"""Worker process: a second-process engine serving shipped plan fragments.

Reference analog: the DN side of the CN->DN plane (the MySQL storage node that
`MyJdbcHandler.java:691` ships physical SQL to) collapsed onto this engine: the
worker boots its own `Instance` (own stores, own metadb, own planner) and
serves:

- exec_sql: run shipped SQL, return columnar results (lane arrays + validity
  + dictionary decode on the string columns, so the coordinator re-encodes
  into its own dictionaries)
- sync:     the inter-node sync-action bus (SyncManagerHelper.java:36) —
  invalidate plan cache / baselines, SET config, stats refresh
- ping:     liveness

Run as a process: `python -m galaxysql_tpu.net.worker --port 0` (prints the
bound port on stdout so a parent can attach).
"""

from __future__ import annotations

import argparse
import collections
import os as _os
import socket
import sys
import threading
import time as _time
import traceback
from typing import Dict, Optional

import numpy as np

from galaxysql_tpu.net.dn import recv_msg, send_msg
from galaxysql_tpu.utils.failpoint import (FAIL_POINTS, FP_WORKER_CRASH,
                                           FP_WORKER_SLOW_DRAIN)


class Worker:
    # bounded exactly-once window: uid -> recorded response.  Sized so a
    # coordinator's retry horizon (seconds) fits comfortably; an evicted uid
    # re-applying would need a retry delayed past 1024 newer writes.
    # In-process by design: the exactly-once guarantee is scoped to a worker
    # process lifetime — transactional DML that must survive a crash rides
    # the XA branch protocol (an uncommitted branch dies with the process),
    # and autocommit uid writes retry within milliseconds while a worker
    # restart takes seconds, so a crash lands those retries on a closed
    # port (typed failure), not on a fresh window.
    DEDUPE_WINDOW = 1024

    def __init__(self, data_dir=None):
        from galaxysql_tpu.server.instance import Instance
        self.instance = Instance(data_dir=data_dir)
        self.queries: list = []  # shipped-SQL log (tests assert pushdown)
        self._lock = threading.Lock()
        # open distributed-txn branches: xid -> Session with an open local txn
        self._branches: Dict[str, object] = {}
        # per-branch execution locks: a deadline-killed coordinator may send
        # xa_rollback on a fresh connection while the branch's DML is STILL
        # executing on another thread — the rollback must wait for the
        # in-flight statement, not tear the session out from under it
        self._branch_locks: Dict[str, threading.RLock] = {}
        # resolved-branch tombstones: a late DML that lost the lock race to
        # its own txn's rollback must NOT auto-recreate the branch (an
        # orphaned open txn invisible to xa_recover); bounded like the
        # dedupe window — xids are unique per txn, never legitimately reused
        self._resolved_xids: "collections.OrderedDict[str, bool]" = \
            collections.OrderedDict()
        # idempotency dedupe window: uid-stamped writes record their response
        # so a reconnect replay returns the recorded result instead of
        # double-applying (the coordinator's retry policy relies on this)
        self._dedupe: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()
        self.dedupe_hits = 0
        # sync-epoch plane: origin node -> last-applied broadcast epoch
        # (persisted in the metadb so a restart keeps the gap detector armed)
        self._sync_epochs: Dict[str, int] = {}
        self.heals = 0
        # in-flight request tokens (GIL-atomic list ops): the queue-depth
        # half of the backpressure piggyback every reply carries
        self._active: list = []

    # -- request handlers ----------------------------------------------------

    def handle(self, header: dict, arrays: Dict[str, np.ndarray]):
        if FAIL_POINTS.active and FAIL_POINTS.rpc_spec(
                FP_WORKER_CRASH, header.get("op")) is not None:
            print(f"FP_WORKER_CRASH fired on {header.get('op')}",
                  file=sys.stderr, flush=True)
            _os._exit(137)  # hard crash: no atexit, no flush — chaos realism
        self._active.append(None)
        try:
            if FAIL_POINTS.active:
                # overload harness: a busy/brownout worker (slow drain) —
                # still alive, still correct, just late; breakers stay
                # closed while queue depth and RTT climb.  The sleep sits
                # INSIDE the active bracket so browned-out requests are
                # visible to the queue-depth piggyback.
                spec = FAIL_POINTS.rpc_spec(FP_WORKER_SLOW_DRAIN,
                                            header.get("op"))
                if spec is not None:
                    _time.sleep(float(spec.get("ms", 25.0)) / 1000.0)
            resp, out = self._handle_epochs(header, arrays)
        finally:
            try:
                self._active.pop()
            except IndexError:  # pragma: no cover - bracket imbalance guard
                pass
        if isinstance(resp, dict):
            # backpressure piggyback: queue depth + memory-pressure tier ride
            # every reply (one list len + one pool division — no syncs)
            try:
                # "up"/"ns" (uptime, history samples) feed the pull-free
                # cluster-health view (Instance.cluster_health(pull=False))
                resp["wl"] = {"q": len(self._active),
                              "mt": self.instance.admission.governor.tier(),
                              "up": round(
                                  _time.time() - self.instance.started_at, 1),
                              "ns": self.instance.metric_history.samples_count}
            except Exception as tex:
                # load telemetry must never fail a data request — but a
                # BROKEN piggyback means the coordinator routes blind, so
                # journal it once instead of swallowing (lint: typed-error
                # discipline); deduped: one event, not one per reply
                from galaxysql_tpu.utils import events
                events.publish(
                    "worker_telemetry_failed",
                    f"load piggyback failed: {type(tex).__name__}: {tex}",
                    severity="warn", dedupe="worker-wl")
        return resp, out

    def _handle_epochs(self, header: dict, arrays: Dict[str, np.ndarray]):
        origin, se = header.get("origin"), header.get("se")
        be = header.get("bcast_epoch")
        want_heal = bool(header.get("heal"))  # coordinator-tracked miss
        epoch = None
        if origin and (se is not None or be is not None):
            origin = str(origin)
            epoch = int(be if be is not None else se)
            want_heal |= self._sync_epoch_gap(origin, epoch,
                                              is_bcast=be is not None)
        if want_heal:
            # heal BEFORE the epoch advances: a failed invalidation raises,
            # the request fails, nothing is recorded — the coordinator keeps
            # its needs_heal flag and the next request retries the heal
            self._heal_caches()
        if epoch is not None:
            self._note_sync_epoch(origin, epoch)
        dl = header.get("deadline_ms")
        if dl is not None:
            # remaining-budget form survives clock skew between processes;
            # handlers check the absolute worker-local deadline
            header["_deadline"] = _time.time() + max(0, int(dl)) / 1000.0
        tr = header.get("trace")
        if tr:
            return self._handle_traced(header, arrays, tr)
        return self._handle(header, arrays)

    # -- sync-epoch healing --------------------------------------------------

    def _last_sync_epoch(self, origin: str) -> Optional[int]:
        """Caller holds self._lock."""
        last = self._sync_epochs.get(origin)
        if last is None:
            v = self.instance.metadb.kv_get(f"sync.epoch.{origin}")
            last = int(v) if v is not None else None
        return last

    def _sync_epoch_gap(self, origin: str, se: int, is_bcast: bool) -> bool:
        """Detect missed SyncBus broadcasts; returns True when a heal is due
        (does NOT advance the stored mark — that happens only after a due
        heal succeeded, or a partially-failed heal would be recorded as
        done and the stale-cache hole would silently reopen).

        Only NON-broadcast requests drive the gap check: they carry the
        coordinator's SETTLED epoch (every broadcast through it has
        completed delivery), so anything beyond this worker's last-applied
        mark means an invalidation never arrived.  Broadcast deliveries
        merely advance the mark — concurrent broadcasts race each other's
        client-lock acquisition, so out-of-order arrival is normal, not a
        gap (a genuinely FAILED delivery is covered by the coordinator's
        needs_heal flag)."""
        with self._lock:
            last = self._last_sync_epoch(origin)
            return not is_bcast and last is not None and se > last

    def _note_sync_epoch(self, origin: str, se: int):
        with self._lock:
            last = self._last_sync_epoch(origin)
            if last is None or se > last:
                self._sync_epochs[origin] = se
                self.instance.metadb.kv_put(f"sync.epoch.{origin}", str(se))

    def _heal_caches(self):
        """Wholesale invalidation (missed-broadcast repair).  Failures
        PROPAGATE: the request must fail rather than record a half-done
        heal as success."""
        from galaxysql_tpu.utils.metrics import SYNC_HEALS
        inst = self.instance
        inst.planner.cache.invalidate_all()
        inst.frag_cache.clear()
        inst.privileges.invalidate_cache()
        with self._lock:
            self.heals += 1
        SYNC_HEALS.inc()
        from galaxysql_tpu.utils import events
        events.publish("sync_heal",
                       "missed sync broadcast detected: plan/fragment/"
                       "privilege caches wholesale-invalidated",
                       node=getattr(inst, "node_id", ""))

    # -- idempotency dedupe window -------------------------------------------

    def _dedupe_execute(self, uid: Optional[str], fn):
        """Exactly-once execution for uid-stamped writes, including the
        CONCURRENT-replay race: a reconnect retry can arrive on a fresh
        connection while the original request is still executing (reply-leg
        loss + immediate retry), so the window holds an in-flight marker —
        the racer parks on the owner's event and replays the recorded
        outcome instead of running the statement a second time."""
        if not uid:
            return fn()
        while True:
            with self._lock:
                ent = self._dedupe.get(uid)
                if ent is None:
                    ev = threading.Event()
                    self._dedupe[uid] = ("pending", ev, None)
                    break  # this request owns the execution
            if ent[0] == "done":
                with self._lock:
                    self.dedupe_hits += 1
                resp = dict(ent[1])
                resp["dedup"] = True
                return resp, ent[2]
            # in flight: wait for the owner to settle, then re-check (a
            # FAILED owner removes the entry and the racer executes fresh)
            if not ent[1].wait(timeout=120.0):
                # the original is STILL running: its outcome is unknown to
                # this replay — flag ambiguity so a write caller takes the
                # unknown-outcome path instead of "statement failed, nothing
                # applied" (the original may yet commit)
                return {"error": f"duplicate of uid {uid} still executing",
                        "ambiguous": True}, {}
        try:
            resp, out = fn()
        except Exception:
            with self._lock:
                self._dedupe.pop(uid, None)
            ev.set()
            raise
        with self._lock:
            if resp.get("error"):
                # failures are not recorded: nothing applied, a retry may
                # legitimately re-execute
                self._dedupe.pop(uid, None)
            else:
                self._dedupe[uid] = ("done", dict(resp), out)
                self._dedupe.move_to_end(uid)
                while len(self._dedupe) > self.DEDUPE_WINDOW:
                    # evict the oldest SETTLED entry; in-flight markers are
                    # skipped (never evicted) but must not dam the window —
                    # a hung statement at the head would otherwise let it
                    # grow without bound
                    victim = next((k for k, v in self._dedupe.items()
                                   if v[0] != "pending"), None)
                    if victim is None:
                        break  # only in-flight markers remain
                    del self._dedupe[victim]
        ev.set()
        return resp, out

    def _handle_traced(self, header: dict, arrays: Dict[str, np.ndarray],
                       tr: dict):
        """Coordinator-injected trace context: run the request under a
        worker-local TraceContext and ship the recorded spans back (plus this
        process's request/reply wall clocks, so the coordinator can correct
        for clock offset before grafting them into the query's tree)."""
        from galaxysql_tpu.utils import tracing
        w_recv = tracing.now_us()
        tc = tracing.TraceContext(int(tr.get("trace_id", 0)),
                                  node=self.instance.node_id)
        with tracing.activate(tc):
            with tc.span(f"worker:{header.get('op')}", kind="worker"):
                resp, out = self._handle(header, arrays)
        resp = dict(resp)
        resp["trace"] = {"w_recv_us": w_recv, "w_send_us": tracing.now_us(),
                         "spans": [s.to_dict() for s in tc.spans]}
        return resp, out

    def _handle(self, header: dict, arrays: Dict[str, np.ndarray]):
        op = header.get("op")
        if op == "ping":
            return {"ok": True, "node": self.instance.node_id}, {}
        def _deadline_gate():
            dl = header.get("_deadline")
            if dl is not None and _time.time() > dl:
                from galaxysql_tpu.utils import errors
                # the propagated deadline passed: abort BEFORE doing work.
                # `unapplied` tells the coordinator nothing executed, so a
                # write caller keeps statement-scoped semantics.
                return {"error": f"deadline exceeded before {op}",
                        "errno": errors.QueryTimeoutError.errno,
                        "unapplied": True}, {}
            return None

        uid = header.get("uid") if op in ("dml", "exec_sql") else None
        if uid:
            # dedupe replay outranks the deadline check: a retry of an
            # already-applied write must report the recorded SUCCESS — a
            # timeout answer would tell the client a write failed that its
            # branch will commit (replay costs nothing anyway)
            handler = self._exec_sql if op == "exec_sql" else self._dml
            return self._dedupe_execute(
                uid, lambda: _deadline_gate() or handler(header))
        gated = _deadline_gate()
        if gated is not None:
            return gated
        if op == "exec_sql":
            return self._exec_sql(header)
        if op == "sync":
            return self._sync(header)
        if op == "exec_plan":
            return self._exec_plan(header)
        if op == "dml":
            return self._dml(header)
        if op == "xa_prepare":
            return self._xa_prepare(header)
        if op == "xa_commit":
            return self._xa_commit(header)
        if op == "xa_rollback":
            return self._xa_rollback(header)
        if op == "xa_recover":
            return self._xa_recover()
        return {"error": f"unknown op {op!r}"}, {}

    # -- distributed-txn branch ops (the DN side of TsoTransaction 2PC,
    # TsoTransaction.java:166-216: per-shard XA PREPARE/COMMIT) --------------

    def _branch_lock(self, xid: str) -> threading.RLock:
        with self._lock:
            lk = self._branch_locks.get(xid)
            if lk is None:
                lk = self._branch_locks[xid] = threading.RLock()
            return lk

    def _tombstone_branch(self, xid: str):
        """Record a resolved xid (called INSIDE the branch lock so a parked
        DML observes it the moment it wakes)."""
        with self._lock:
            self._resolved_xids[xid] = True
            while len(self._resolved_xids) > self.DEDUPE_WINDOW * 4:
                self._resolved_xids.popitem(last=False)

    def _dml(self, header: dict):
        """Execute shipped DML inside the branch's open local transaction."""
        from galaxysql_tpu.server.session import Session
        xid = header["xid"]
        with self._branch_lock(xid):
            with self._lock:
                self.queries.append(header["sql"])
                s = self._branches.get(xid)
                if s is None and xid in self._resolved_xids:
                    # this branch already committed/rolled back — a late DML
                    # that lost the lock race must not resurrect it as an
                    # orphaned open transaction
                    return {"error":
                            f"branch {xid!r} already resolved"}, {}
                if s is None:
                    s = Session(self.instance,
                                schema=header.get("schema") or None)
                    s.autocommit = False
                    s._begin()
                    self._branches[xid] = s
            if header.get("schema"):
                s.schema = header["schema"]
            rs = self._with_deadline(
                s, header.get("_deadline"),
                lambda: s.execute(header["sql"], header.get("params") or []))
            return {"ok": True, "affected": rs.affected}, {}

    _UNSET = object()

    @classmethod
    def _with_deadline(cls, sess, deadline, fn):
        """Run `fn` with the remaining deadline budget handed to the nested
        session as its own MAX_EXECUTION_TIME (drain-boundary checks enforce
        it); shared by the shipped-SQL and branch-DML handlers.  Branch
        sessions are long-lived, so any pre-existing session value is
        restored, not dropped."""
        if deadline is None:
            return fn()
        prior = sess.vars.get("MAX_EXECUTION_TIME", cls._UNSET)
        sess.vars["MAX_EXECUTION_TIME"] = \
            max(1, int((deadline - _time.time()) * 1000))
        try:
            return fn()
        finally:
            if prior is cls._UNSET:
                sess.vars.pop("MAX_EXECUTION_TIME", None)
            else:
                sess.vars["MAX_EXECUTION_TIME"] = prior

    def _xa_prepare(self, header: dict):
        import json
        from galaxysql_tpu.txn.xa import participants_of
        xid = header["xid"]
        with self._branch_lock(xid):
            return self._xa_prepare_locked(header, xid, json,
                                           participants_of)

    def _xa_prepare_locked(self, header, xid, json, participants_of):
        s = self._branches.get(xid)
        if s is None or s.txn is None:
            return {"ok": False, "error": f"unknown branch {xid!r}"}, {}
        parts = participants_of(s.txn)
        for sp in parts:
            if not sp.prepare():
                for done in parts:
                    done.rollback()
                self._branches.pop(xid, None)
                s.txn = None
                s.close()  # deregister: a leaked session reads as an open txn
                return {"ok": False, "error": "branch prepare failed"}, {}
        # durability order: store snapshots FIRST, marker LAST — a crash before
        # the marker means prepare was never acked (presumed abort is correct);
        # after the marker the provisional rows are on disk and recovery holds
        # them in doubt (recover_persisted skips marked branches)
        self.instance.save()
        self.instance.metadb.kv_put(
            f"xa.branch.{xid}",
            json.dumps({"txn_id": s.txn.txn_id, "state": "PREPARED"}))
        return {"ok": True}, {}

    def _branch_txn_id(self, xid: str):
        import json
        v = self.instance.metadb.kv_get(f"xa.branch.{xid}")
        if v is None:
            return None
        try:
            return int(json.loads(v)["txn_id"])
        except Exception:  # galaxylint: disable=swallow -- kv probe: None means no such branch, the caller's contract
            return None

    def _finalize_stamps(self, txn_id: int, commit_ts):
        """Resolve ±txn_id provisional stamps across all stores (used when the
        branch session died with the process; mirrors recover_persisted)."""
        from galaxysql_tpu.storage.table_store import INFINITY_TS
        own = -txn_id
        for store in self.instance.stores.values():
            for p in store.partitions:
                with p.lock:
                    if commit_ts is not None:
                        p.begin_ts[p.begin_ts == own] = commit_ts
                        p.end_ts[p.end_ts == own] = commit_ts
                    else:
                        p.end_ts[p.end_ts == own] = INFINITY_TS
                        mine = p.begin_ts == own
                        p.begin_ts[mine] = INFINITY_TS
                        p.end_ts[mine] = 0
            store.table.bump_version()
        self.instance.catalog.version += 1

    def _xa_commit(self, header: dict):
        import json
        from galaxysql_tpu.txn.xa import participants_of
        xid = header["xid"]
        with self._branch_lock(xid):
            out = self._xa_commit_locked(header, xid, json, participants_of)
            self._tombstone_branch(xid)
        with self._lock:
            # branch resolved: drop its lock entry (unique xids would
            # otherwise leak one RLock per distributed txn forever)
            self._branch_locks.pop(xid, None)
        return out

    def _xa_commit_locked(self, header, xid, json, participants_of):
        commit_ts = int(header["commit_ts"])
        # the coordinator's TSO is the clock: local snapshots must advance past
        # the commit stamp or the new rows would be invisible to local reads
        self.instance.tso.observe(commit_ts)
        s = self._branches.pop(xid, None)
        if s is not None and s.txn is not None:
            txn = s.txn
            s.txn = None
            for sp in participants_of(txn):
                sp.commit(commit_ts)
            self.instance.cdc.flush_txn(txn, commit_ts)
            self.instance.catalog.version += 1
            s.close()
            txn_id = txn.txn_id
        else:
            txn_id = self._branch_txn_id(xid)
            if txn_id is None:
                # idempotent: branch already resolved (re-sent commit)
                return {"ok": True, "already": True}, {}
            self._finalize_stamps(txn_id, commit_ts)
        self.instance.metadb.tx_log_put(txn_id, "DONE", commit_ts)
        self.instance.metadb.kv_put(f"xa.branch.{xid}",
                                    json.dumps({"txn_id": txn_id,
                                                "state": "DONE"}))
        self.instance.save()
        return {"ok": True}, {}

    def _xa_rollback(self, header: dict):
        import json
        from galaxysql_tpu.txn.xa import participants_of
        xid = header["xid"]
        # serialized against an in-flight _dml on the same branch: roll back
        # only AFTER the statement settles, never mid-execution
        with self._branch_lock(xid):
            out = self._xa_rollback_locked(header, xid, json,
                                           participants_of)
            self._tombstone_branch(xid)
        with self._lock:
            self._branch_locks.pop(xid, None)  # branch resolved
        return out

    def _xa_rollback_locked(self, header, xid, json, participants_of):
        s = self._branches.pop(xid, None)
        if s is not None and s.txn is not None:
            txn = s.txn
            s.txn = None
            for sp in participants_of(txn):
                sp.rollback()
            s.close()
            txn_id = txn.txn_id
        else:
            txn_id = self._branch_txn_id(xid)
            if txn_id is None:
                return {"ok": True, "already": True}, {}
            self._finalize_stamps(txn_id, None)
        self.instance.metadb.tx_log_put(txn_id, "ABORTED")
        self.instance.metadb.kv_put(f"xa.branch.{xid}",
                                    json.dumps({"txn_id": txn_id,
                                                "state": "ABORTED"}))
        self.instance.save()
        return {"ok": True}, {}

    def _xa_recover(self):
        """List PREPARED (in-doubt) branches for the coordinator to resolve."""
        import json
        xids = []
        for k, v in self.instance.metadb.kv_scan("xa.branch."):
            try:
                if json.loads(v).get("state") == "PREPARED":
                    xids.append(k[len("xa.branch."):])
            except Exception:  # galaxylint: disable=swallow -- one corrupt branch record must not hide the other in-doubt xids
                continue
        return {"ok": True, "xids": xids}, {}

    def _exec_sql(self, header: dict):
        import contextlib
        from galaxysql_tpu.server.session import Session
        from galaxysql_tpu.utils import tracing
        sql = header["sql"]
        with self._lock:
            self.queries.append(sql)
        tc = tracing.current()

        def scope(name):
            return tc.span(name, kind="operator") if tc is not None \
                else contextlib.nullcontext()
        # an xid routes the statement through that branch's open session so
        # reads observe the branch's own uncommitted writes (the degrade path
        # must keep the same txn visibility the fragment path has)
        branch = self._branches.get(header.get("xid")) \
            if header.get("xid") else None
        dl = header.get("_deadline")
        if branch is not None:
            if header.get("schema"):
                branch.schema = header["schema"]
            with scope("execute"):
                rs = self._with_deadline(branch, dl,
                                         lambda: branch.execute(sql))
            with scope("serialize"):
                return self._serialize_rs(rs)
        s = Session(self.instance, schema=header.get("schema") or None)
        try:
            with scope("execute"):
                rs = self._with_deadline(s, dl, lambda: s.execute(sql))
            with scope("serialize"):
                return self._serialize_rs(rs)
        finally:
            s.close()

    @staticmethod
    def _serialize_rs(rs):
        """ResultSet -> wire response (shared by the plain and branch paths)."""
        cols = rs.names
        arrays: Dict[str, np.ndarray] = {}
        types = []
        batch_cols = None
        if rs.batch is not None:
            bc = rs.batch.compact()
            if len(bc.names()) == len(rs.names):
                batch_cols = [bc.columns[n] for n in bc.names()]
        for i, (name, typ) in enumerate(zip(rs.names, rs.types)):
            vals = [r[i] for r in rs.rows]
            valid = np.array([v is not None for v in vals], dtype=bool)
            if typ.is_string:
                data = np.array([v if v is not None else "" for v in vals],
                                dtype=object).astype(str)
            elif typ.sql_name().startswith("DECIMAL") and batch_cols is not None:
                # lane-exact: scaled int64 straight from the engine lane —
                # a float round-trip truncates >15-16 significant digits
                data = batch_cols[i].np_data().astype(np.int64)
                arrays[f"d::{name}"] = data
                if not valid.all():
                    arrays[f"v::{name}"] = valid
                types.append(typ.sql_name() + "#scaled")
                continue
            elif typ.sql_name().startswith(("DECIMAL", "DOUBLE", "FLOAT")):
                data = np.array([v if v is not None else 0.0 for v in vals],
                                dtype=np.float64)
            elif typ.sql_name() in ("DATE", "DATETIME"):
                data = np.array([v if v is not None else "" for v in vals],
                                dtype=object).astype(str)
            else:
                data = np.array([v if v is not None else 0 for v in vals],
                                dtype=np.int64)
            arrays[f"d::{name}"] = data
            if not valid.all():
                arrays[f"v::{name}"] = valid
            types.append(typ.sql_name())
        return ({"columns": cols, "types": types, "rows": len(rs.rows),
                 "affected": rs.affected}, arrays)

    _SARG_OPS = {"eq": np.equal, "lt": np.less, "le": np.less_equal,
                 "gt": np.greater, "ge": np.greater_equal}

    @staticmethod
    def _wire_lane(tm, cname: str, lane: np.ndarray):
        """Lane -> wire array + type tag: the ONE encoder for fragment results
        and deleted-key lists (strings decode via the dictionary, DATE/DATETIME
        format to text, DECIMAL ships scaled int64 tagged '#scaled')."""
        cm = tm.column(cname)
        tname = cm.dtype.sql_name()
        if cm.dtype.is_string:
            d = tm.dictionaries.get(cname.lower())
            vals = d.decode(lane) if d is not None else [""] * lane.size
            arr = np.array([x if x is not None else "" for x in vals],
                           dtype=object).astype(str) if lane.size else \
                np.zeros(0, dtype="U1")
            return arr, tname
        if tname.startswith("DECIMAL"):
            return lane.astype(np.int64), tname + "#scaled"
        if tname in ("DATE", "DATETIME"):
            from galaxysql_tpu.types import temporal
            fmt = temporal.format_date if tname == "DATE" \
                else temporal.format_datetime
            arr = np.array([fmt(int(x)) for x in lane],
                           dtype=object).astype(str) if lane.size else \
                np.zeros(0, dtype="U1")
            return arr, tname
        if tname in ("DOUBLE", "FLOAT"):
            return lane.astype(np.float64), tname
        return lane.astype(np.int64), tname

    def _exec_plan(self, header: dict):
        """Execute a shipped physical scan fragment straight against the store.

        Reference analog: `PolarxExecPlan` key-Get/scan execution
        (`MyJdbcHandler.java:691-742`, `RelToXPlanConverter.java:41`): the
        coordinator ships a bound fragment — table, pruned column list,
        lane-domain SARGs, optional point key — and the worker runs it with
        zero parse/plan work.  Unsupported shapes raise; the coordinator
        degrades to SQL text (`XPlanTemplate.java:132` fallback)."""
        f = header["fragment"]
        with self._lock:
            self.queries.append(f"PLAN:{f['schema']}.{f['table']}"
                                f":{','.join(f['columns'])}")
        inst = self.instance
        tm = inst.catalog.table(f["schema"], f["table"])
        store = inst.store(f["schema"], f["table"])
        snapshot = inst.tso.next_timestamp()
        # read-your-own-writes across the seam: a fragment carrying the
        # session's branch xid sees that branch's provisional rows (the
        # reference reads through the txn-bound DN connection)
        txn_id = 0
        bs = self._branches.get(f.get("xid")) if f.get("xid") else None
        if bs is not None and bs.txn is not None:
            txn_id = bs.txn.txn_id
        point = f.get("point")
        lane_point = None
        if point is not None:
            # the CN ships point keys ALREADY in lane domain (scan.point_eq is
            # _lane_encode'd there); re-encoding would double-scale decimals
            lane_point = point[1]
        sargs = f.get("sargs") or []
        since = f.get("since")  # delta reads (online table move catchup)
        del_of = f.get("deleted_since_of")
        cols_out: Dict[str, list] = {c: [] for c in f["columns"]}
        valid_out: Dict[str, list] = {c: [] for c in f["columns"]}
        deleted_keys: list = []
        # traced fragments: scan / rf-prune / serialize child spans under the
        # worker root (grafted into the coordinator's tree by the RPC layer)
        import contextlib
        from galaxysql_tpu.utils import tracing
        tc = tracing.current()
        scan_scope = tc.span("scan", kind="operator",
                             table=f"{f['schema']}.{f['table']}") \
            if tc is not None else contextlib.nullcontext()
        # rf-prune attribution is traced-only: counting surviving rows costs
        # an O(partition) sum the untraced fragment path must not pay
        rf_clock = [0.0, 0] \
            if tc is not None and (f.get("rf_in") or sargs) else None
        with scan_scope:
            err = self._exec_plan_scan(f, store, snapshot, txn_id, lane_point,
                                       point, sargs, since, del_of, cols_out,
                                       valid_out, deleted_keys, rf_clock,
                                       deadline=header.get("_deadline"))
        if err is not None:
            return err, {}
        if rf_clock is not None:
            tc.add("rf-prune", kind="operator",
                   dur_us=round(rf_clock[0] * 1e6, 1),
                   rows_pruned=rf_clock[1])
        ser_scope = tc.span("serialize", kind="operator") \
            if tc is not None else contextlib.nullcontext()
        with ser_scope:
            return self._exec_plan_reply(f, tm, del_of, cols_out, valid_out,
                                         deleted_keys, snapshot)

    def _exec_plan_scan(self, f, store, snapshot, txn_id, lane_point, point,
                        sargs, since, del_of, cols_out, valid_out,
                        deleted_keys, rf_clock, deadline=None):
        import time as _t
        from galaxysql_tpu.utils import errors as _err
        for p in store.partitions:
            if deadline is not None and _t.time() > deadline:
                # partition boundary = the worker's drain boundary: abort the
                # fragment typed instead of finishing a doomed scan
                raise _err.QueryTimeoutError(
                    f"fragment deadline exceeded scanning "
                    f"{f['schema']}.{f['table']}")
            if p.num_rows == 0:
                continue
            with p.lock:
                if lane_point is not None:
                    ids = p.key_candidates(point[0], lane_point)
                    if ids.size == 0:
                        continue
                    from galaxysql_tpu import native as _native
                    # visibility over the CANDIDATE slice only — a full-lane
                    # mask would cost O(partition) on the point hot path
                    keep = p.valid[point[0]][ids] & _native.visible_mask(
                        p.begin_ts[ids], p.end_ts[ids], snapshot, txn_id)
                    ids = ids[keep]
                else:
                    vis = p.visible_mask(snapshot, txn_id)
                    if since is not None:
                        vis = vis & (p.begin_ts > int(since))
                    t_rf = _t.perf_counter() if rf_clock is not None else 0.0
                    before = int(vis.sum()) if rf_clock is not None else 0
                    for col, op, val in sargs:
                        opf = self._SARG_OPS.get(op)
                        if opf is None:
                            return {"error": f"unsupported sarg op {op!r}"}
                        lane = p.lanes[col]
                        # integer lanes compare in int64 — a float64 cast
                        # collapses values beyond 2^53 and worker-side
                        # exclusion is load-bearing (rows never reach the CN)
                        if isinstance(val, int) and \
                                np.issubdtype(lane.dtype, np.integer):
                            vis = vis & p.valid[col] & \
                                opf(lane.astype(np.int64), np.int64(val))
                        else:
                            vis = vis & p.valid[col] & \
                                opf(lane.astype(np.float64), float(val))
                    for col, vals in (f.get("rf_in") or []):
                        # runtime-filter IN-list (small join build sides):
                        # exact membership prune before rows cross the seam
                        lane = p.lanes[col]
                        arr = np.asarray(vals)
                        vis = vis & p.valid[col] & \
                            np.isin(lane, arr.astype(lane.dtype, copy=False))
                    ids = np.nonzero(vis)[0]
                    if rf_clock is not None:
                        # rf-prune attribution (host-side): time + rows
                        # removed by SARGs/IN-lists, summed over partitions
                        rf_clock[0] += _t.perf_counter() - t_rf
                        rf_clock[1] += before - int(ids.size)
                if del_of is not None:
                    dmask = (p.end_ts >= 0) & (p.end_ts > int(since or 0)) & \
                        (p.end_ts <= snapshot)
                    if dmask.any():
                        deleted_keys.append(p.lanes[del_of][dmask])
                if ids.size == 0:
                    continue
                for c in f["columns"]:
                    cols_out[c].append(p.lanes[c][ids])
                    valid_out[c].append(p.valid[c][ids])
        return None

    def _exec_plan_reply(self, f, tm, del_of, cols_out, valid_out,
                         deleted_keys, snapshot):
        """Wire-encode the gathered lanes (the `serialize` span's work)."""
        arrays: Dict[str, np.ndarray] = {}
        types = []
        for c in f["columns"]:
            lane = (np.concatenate(cols_out[c]) if cols_out[c]
                    else np.zeros(0, dtype=tm.column(c).dtype.lane))
            v = (np.concatenate(valid_out[c]) if valid_out[c]
                 else np.zeros(0, dtype=np.bool_))
            arr, tname = self._wire_lane(tm, c, lane)
            arrays[f"d::{c}"] = arr
            if lane.size and not bool(v.all()):
                arrays[f"v::{c}"] = v
            types.append(tname)
        if del_of is not None:
            dk = (np.concatenate(deleted_keys) if deleted_keys
                  else np.zeros(0, dtype=np.int64))
            # wire-value domain (decoded strings / formatted dates / scaled
            # ints) so the caller's DELETE literals match what it inserted
            arrays["deleted::keys"], _ = self._wire_lane(tm, del_of, dk)
        n = int(arrays[f"d::{f['columns'][0]}"].shape[0]) if f["columns"] else 0
        return ({"columns": list(f["columns"]), "types": types, "rows": n,
                 "affected": 0, "snapshot": snapshot}, arrays)

    def _sync(self, header: dict):
        """Sync-action bus (SyncManagerHelper analog)."""
        action = header.get("action")
        payload = header.get("payload") or {}
        inst = self.instance
        if action == "invalidate_plan_cache":
            inst.planner.cache.invalidate_all()
            return {"ok": True, "action": action}, {}
        if action == "invalidate_fragment_cache":
            # a coordinator wrote to a table this node may hold cached
            # fragments for: bump the epoch (remote-keyed fragments) and drop
            # resident entries (exec/fragment_cache.py invalidation plane)
            key = payload.get("table_key") or \
                (f"{payload.get('schema', '').lower()}"
                 f".{payload.get('table', '').lower()}")
            inst.frag_cache.bump_epoch(key)
            return {"ok": True, "action": action}, {}
        if action == "invalidate_baselines":
            for row in list(inst.planner.spm.rows()):
                inst.planner.spm.delete(row[0])
            return {"ok": True, "action": action}, {}
        if action == "set_config":
            inst.config.set_instance(payload["name"], payload["value"])
            return {"ok": True, "action": action}, {}
        if action == "table_meta":
            tm = inst.catalog.table(payload["schema"], payload["table"])
            return {"ok": True,
                    "columns": [[c.name, c.dtype.sql_name().split("(")[0],
                                 c.dtype.precision, c.dtype.scale, c.nullable]
                                for c in tm.columns],
                    "primary_key": list(tm.primary_key)}, {}
        if action == "query_log":
            with self._lock:
                return {"ok": True, "queries": list(self.queries)}, {}
        if action == "failpoint":
            # remote fault arming for the chaos harness: the coordinator (or
            # a test) plants worker-side failpoints (e.g. FP_WORKER_CRASH)
            if payload.get("clear"):
                FAIL_POINTS.clear()
            elif payload.get("disarm"):
                FAIL_POINTS.disarm(payload["key"])
            else:
                FAIL_POINTS.arm(payload["key"], payload.get("value", True))
            return {"ok": True, "action": action}, {}
        if action == "worker_stats":
            # fault-tolerance observability: dedupe window, sync-epoch heals
            with self._lock:
                return {"ok": True, "node": inst.node_id,
                        "dedupe_entries": len(self._dedupe),
                        "dedupe_hits": self.dedupe_hits,
                        "heals": self.heals,
                        "sync_epochs": dict(self._sync_epochs)}, {}
        if action == "health":
            # SLO-plane cluster view: workers run the same sampler over
            # their own registries (the Worker's Instance constructs one);
            # a health pull takes an interval-gated sample, then reports a
            # snapshot summary — pull-driven, so an idle worker pays zero
            mh = inst.metric_history
            mh.maybe_sample()
            return {"ok": True, "action": action, "node": inst.node_id,
                    "uptime_s": round(_time.time() - inst.started_at, 3),
                    "active": float(len(self._active)),
                    "qps": round(mh.rate("queries_total"), 3),
                    "error_rate": round(mh.rate("query_errors"), 6),
                    "mem_tier": int(inst.admission.governor.tier()),
                    "samples": int(mh.summary()["samples"]),
                    "burning": inst.slo.burning_names()}, {}
        return {"error": f"unknown sync action {action!r}"}, {}

    # -- server loop ---------------------------------------------------------

    def serve(self, host: str = "127.0.0.1", port: int = 0):
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(16)
        self.port = srv.getsockname()[1]
        print(f"WORKER_READY {self.port}", flush=True)
        while True:
            conn, _ = srv.accept()
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket):
        from galaxysql_tpu.utils import errors as _err
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            while True:
                header, arrays = recv_msg(conn)
                try:
                    resp, out = self.handle(header, arrays)
                except Exception as e:
                    traceback.print_exc(file=sys.stderr)
                    # typed errors keep their errno across the wire so the
                    # coordinator re-raises the same class (QueryTimeoutError
                    # must not come back as a generic TddlError)
                    resp, out = {"error": f"{type(e).__name__}: {e}",
                                 "errno": int(getattr(e, "errno", 1105)
                                              or 1105)}, {}
                try:
                    send_msg(conn, resp, out)
                except _err.ProtocolError as pe:
                    # the RESULT was oversized: encode_msg rejected it before
                    # any byte shipped, so the stream is still aligned —
                    # reply typed instead of dropping a healthy connection
                    # (and triggering coordinator retries of the same query)
                    send_msg(conn, {"error": str(pe), "errno": pe.errno}, {})
        except (ConnectionError, OSError):
            pass
        except _err.ProtocolError:
            # corrupt frame: the stream is unrecoverable — drop the conn
            traceback.print_exc(file=sys.stderr)
        finally:
            conn.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--platform", default=None,
                    help="force the jax platform (e.g. cpu) in-process, "
                         "before first device use")
    ap.add_argument("--init-sql", default=None,
                    help="semicolon-separated bootstrap statements")
    args = ap.parse_args()
    import os
    import jax
    from galaxysql_tpu import runtime
    platform = args.platform or os.environ.get("GALAXYSQL_WORKER_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    runtime.enable_compile_cache()
    w = Worker(data_dir=args.data_dir)
    if args.init_sql:
        from galaxysql_tpu.server.session import Session
        s = Session(w.instance)
        s.execute(args.init_sql)
        s.close()
    w.serve(port=args.port)


if __name__ == "__main__":
    main()
