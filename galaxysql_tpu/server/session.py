"""Session: per-connection state + statement dispatch.

Reference analog: `ServerConnection` (§2.2) — schema selection, autocommit/transaction
lifecycle, and `innerExecute` as the top of every query.  DQL goes parse -> plan ->
operators; DML runs the TP host path against the MVCC store; DDL/SET/SHOW/USE handled
inline (the reference's 133 logical handlers, §2.6, are this dispatch).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from galaxysql_tpu import runtime
from galaxysql_tpu.chunk.batch import ColumnBatch, Dictionary, concat_batches
from galaxysql_tpu.exec.operators import run_to_batch
from galaxysql_tpu.expr import ir
from galaxysql_tpu.expr.compiler import ExprCompiler, _find_dictionary
from galaxysql_tpu.meta.catalog import (ColumnMeta, IndexMeta, PartitionInfo, TableMeta,
                                        SINGLE)
from galaxysql_tpu.plan import logical as L
from galaxysql_tpu.plan.binder import Binder, Scope
from galaxysql_tpu.plan.physical import ExecContext, build_operator
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.sql import ast
from galaxysql_tpu.sql.lexer import split_statements
from galaxysql_tpu.sql.parameterize import DecimalParam, parameterize
from galaxysql_tpu.sql.parser import parse
from galaxysql_tpu.storage.table_store import INFINITY_TS
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import errors, tracing
from galaxysql_tpu.utils.ccl import GLOBAL_CCL
from galaxysql_tpu.utils.failpoint import FAIL_POINTS, FP_SLO_LATENCY_MS


@dataclasses.dataclass
class ResultSet:
    names: List[str]
    types: List[dt.DataType]
    rows: List[Tuple]
    affected: int = 0
    last_insert_id: int = 0
    info: str = ""
    # compacted result ColumnBatch (queries only): lane-exact values for callers
    # that re-encode columns — the worker wire plane ships DECIMAL lanes from
    # here instead of the float round-trip of the Python rows
    batch: Any = None

    @property
    def is_query(self) -> bool:
        return bool(self.names)


def ok(affected: int = 0, info: str = "", last_insert_id: int = 0) -> ResultSet:
    return ResultSet([], [], [], affected, last_insert_id, info)


import contextlib

_NULL_CTX = contextlib.nullcontext()
_CPU_DEVICE = None


def _cpu_device_ctx():
    """Pin for the TP host path.  The CPU backend is part of every supported
    start-up (`galaxysql_tpu/__init__` keeps it beside the accelerator); if it
    is absent the backend's own error surfaces here — TP statements never
    run on the accelerator unnoticed."""
    global _CPU_DEVICE
    import jax
    if _CPU_DEVICE is None:
        _CPU_DEVICE = jax.local_devices(backend="cpu")[0]
    return jax.default_device(_CPU_DEVICE)


class Transaction:
    """TSO transaction: snapshot at begin, provisional (-txn_id) stamps on writes,
    finalized to a fresh commit timestamp at COMMIT (TsoTransaction analog, §3.4)."""

    def __init__(self, ts: int):
        self.snapshot_ts = ts
        self.txn_id = ts  # TSO values are unique; the snapshot doubles as txn id
        # (store, pid, start_row, n) appended ranges awaiting commit stamp
        self.inserted: List[Tuple[Any, int, int, int]] = []
        # (store, pid, row_ids, old_end_ts) provisional deletes
        self.deleted: List[Tuple[Any, int, np.ndarray, np.ndarray]] = []
        # worker branches of this txn: (host, port) -> xid (TsoTransaction's
        # per-shard XA branches; committed via the 2PC coordinator)
        self.remote: Dict[Tuple[str, int], str] = {}
        # (schema, table) of worker-resident tables this txn wrote: fragment
        # epochs bump again AFTER commit/rollback — the statement-time bump
        # alone leaves a window where a peer re-caches pre-commit state under
        # the new epoch and never hears about the commit
        self.remote_tables: set = set()

    def touched_tables(self):
        seen = {}
        for store, *_ in self.inserted + self.deleted:
            seen[id(store)] = store
        return seen.values()


def gsi_targets(instance, tm):
    out = []
    for i in tm.indexes:
        if i.global_index and i.status in ("WRITE_ONLY", "PUBLIC"):
            gsi_name = f"{tm.name}${i.name}"
            try:
                gtm = instance.catalog.table(tm.schema, gsi_name)
                out.append((i, gtm, instance.store(tm.schema, gsi_name)))
            except (errors.UnknownTableError, KeyError):
                pass
    return out


def gsi_write_rows(instance, tm, base_store, pid: int, start: int, n: int,
                   ts: int, txn):
    """Propagate base rows appended at [start, start+n) into every GSI store.

    Writes carry the same (possibly provisional) timestamp and register with
    the transaction so COMMIT finalizes and ROLLBACK undoes them with the
    base rows."""
    targets = gsi_targets(instance, tm)
    if not targets or n == 0:
        return
    p = base_store.partitions[pid]
    for _i, gtm, gstore in targets:
        cols = gtm.column_names()
        lanes = {c: p.lanes[c][start:start + n] for c in cols}
        valid = {c: p.valid[c][start:start + n] for c in cols}
        pids = gstore._route(lanes)
        # the GSI store's append_lock: the (before, append) pair below must
        # not interleave with another GSI writer's appends or the undo range
        # would cover the other writer's rows (same race append_lock closes
        # on the base store)
        with gstore.append_lock:
            for gp in np.unique(pids):
                sel = np.nonzero(pids == gp)[0]
                gpart = gstore.partitions[int(gp)]
                before = gpart.num_rows
                gpart.append({k: v[sel] for k, v in lanes.items()},
                             {k: v[sel] for k, v in valid.items()}, ts)
                if txn is not None:
                    txn.inserted.append((gstore, int(gp), before, sel.size))


def _pk_void(arrays: List[np.ndarray]) -> np.ndarray:
    """Pack parallel key arrays into one comparable lane (exact tuple
    matching — per-column isin would match the cross product of composite
    keys)."""
    return np.rec.fromarrays(arrays)


def gsi_delete(instance, tm, base_store, pid: int, row_ids: np.ndarray,
               ts: int, txn):
    """Remove the GSI entries of deleted base rows, matched on primary key."""
    if not tm.primary_key:
        return
    targets = gsi_targets(instance, tm)
    if not targets:
        return
    p = base_store.partitions[pid]
    del_keys = _pk_void([p.lanes[c][row_ids] for c in tm.primary_key])
    for _i, gtm, gstore in targets:
        if not all(gtm.has_column(c) for c in tm.primary_key):
            continue
        for gp_id, gp in enumerate(gstore.partitions):
            vis = gp.visible_mask(None)
            keys = _pk_void([gp.lanes[c] for c in tm.primary_key])
            mask = vis & np.isin(keys, del_keys)
            ids = np.nonzero(mask)[0]
            if ids.size:
                if txn is not None:
                    txn.deleted.append((gstore, gp_id, ids,
                                        gp.end_ts[ids].copy()))
                gp.delete_rows(ids, ts)


class Session:
    # bound on each replica DML leg (a hung replica goes stale after this,
    # it must not stall the statement for socket-timeout x retries)
    REPLICA_DML_TIMEOUT_S = 30.0

    def __init__(self, instance: Instance, schema: Optional[str] = None):
        self.instance = instance
        self.conn_id = instance.allocate_conn_id()
        self.schema = schema
        self.autocommit = True
        self.txn: Optional[Transaction] = None
        self.vars: Dict[str, Any] = {}
        self.user_vars: Dict[str, Any] = {}
        self.user = "root"
        self.last_trace: List[str] = []
        self.last_spans: List[Any] = []  # last traced query's span tree
        # the CURRENT statement's TraceContext while a jax.profiler session
        # records (its phase ramps become profiler annotations); else None
        self._ann = None
        # router trace hint for the CURRENT statement: (trace_id, parent
        # span id, origin node, sampled) parsed off the statement prefix by
        # _execute_one; None for locally-originated statements
        self._trace_hint: Optional[tuple] = None
        # per-statement MAX_EXECUTION_TIME deadline (absolute seconds, None =
        # unlimited): set at statement entry, threaded into ExecContext and
        # worker RPC headers
        self._deadline: Optional[float] = None
        instance.sessions[self.conn_id] = self

    # -- public API -----------------------------------------------------------

    def execute(self, sql: str, params: Optional[list] = None) -> ResultSet:
        """Run statement(s); returns the LAST result (embedded convenience API)."""
        results = self.execute_all(sql, params)
        return results[-1] if results else ok()

    def execute_all(self, sql: str, params: Optional[list] = None) -> List[ResultSet]:
        """Run every statement, returning each result (the wire protocol sends all)."""
        if ";" not in sql:
            # single statement: skip the tokenizing splitter (TP point-query
            # latency — the split exists only to find ';' outside literals)
            return [self._execute_one(sql, params)] if sql.strip() else [ok()]
        stmts = split_statements(sql)
        return [self._execute_one(s, params) for s in stmts] if stmts else [ok()]

    def close(self):
        # session exit ramp: a failed rollback must NOT leak the session's
        # advisory locks or registry entry (other sessions would block on
        # GET_LOCK forever) — and must not vanish silently either: the
        # failure lands in the journal as a severity-tagged event
        try:
            if self.txn is not None:
                self._rollback()
        except Exception as rex:
            from galaxysql_tpu.utils import events
            events.publish(
                "session_close_failed",
                f"rollback on session close failed for conn "
                f"{self.conn_id}: {type(rex).__name__}: {rex}",
                severity="warn", node=self.instance.node_id)
        finally:
            self.instance.locks.release_all(self.conn_id)
            self.instance.sessions.pop(self.conn_id, None)

    def _lock_fn(self, name: str, vals: list):
        """GET_LOCK family (LockingFunctionManager.java analog)."""
        lm = self.instance.locks
        key = str(vals[0])
        if name == "get_lock":
            timeout = float(vals[1]) if len(vals) > 1 else 0.0
            return lm.get_lock(key, timeout, self.conn_id)
        if name == "release_lock":
            return lm.release_lock(key, self.conn_id)
        if name == "is_free_lock":
            return lm.is_free_lock(key)
        return lm.is_used_lock(key)

    # -- dispatch ----------------------------------------------------------------

    _SELECT_RE = __import__("re").compile(
        r"^\s*(?:/\*.*?\*/\s*)*select\b", __import__("re").I | __import__("re").S)
    _DML_RE = __import__("re").compile(
        r"^\s*(?:insert|update|delete)\b", __import__("re").I)
    # cross-coordinator trace hint: `/*trace:<id>:<parent>:<node>:<0|1>*/`
    # prefixed by RouterSession onto routed statements.  Parsed and STRIPPED
    # here — before digesting/parameterization — so plan-cache keys and
    # statement-summary digests never fragment per trace id.
    _TRACE_HINT_RE = __import__("re").compile(
        r"^/\*trace:(\d+):(\d+):([^:*]*):([01])\*/\s*")

    def _execute_one(self, sql: str, params: Optional[list]) -> ResultSet:
        # one startswith per statement on the hot path; the regex runs only
        # for statements that actually carry the router's hint prefix
        if sql.startswith("/*trace:"):
            m = self._TRACE_HINT_RE.match(sql)
            if m is not None:
                self._trace_hint = (int(m.group(1)), int(m.group(2)),
                                    m.group(3), m.group(4) == "1")
                sql = sql[m.end():]
        elif self._trace_hint is not None:
            self._trace_hint = None  # hint covers exactly one statement
        # statement deadline: one config lookup; MAX_EXECUTION_TIME=0 (the
        # default) keeps the hot path at a None check everywhere downstream
        ms = self.instance.config.get("MAX_EXECUTION_TIME", self.vars)
        self._deadline = time.time() + ms / 1000.0 if ms else None
        if self._SELECT_RE.match(sql):
            # SELECT hot path: the plan cache keys on the PARAMETERIZED text and
            # carries the AST, so re-parsing the raw text (distinct per literal,
            # ~1ms) per execution is pure waste; authorization runs against the
            # plan's AST in _run_query_admitted (TP latency floor, SURVEY §3.2)
            return self._run_query(None, sql, params)
        if self.txn is None and self.instance.dml_plans and \
                "/*" not in sql and self._DML_RE.match(sql):
            # DML hot path, the write-side mirror of the SELECT one: a
            # registered batch plan executes without parse or bind, coalesced
            # with plan-identical statements from concurrent sessions
            # (server/dml_batch.py).  Hinted statements never take it.
            # The WHOLE statement (batched or sequential fallback) brackets
            # the scheduler's in-flight gate: live DML concurrency is the
            # signal the adaptive window keys off.
            sched = getattr(self.instance, "dml_batch_scheduler", None)
            if sched is not None:
                sched.point_begin()
                try:
                    rs = self._try_batched_dml(sql, params)
                    if rs is not None:
                        return rs
                    stmt = parse(sql)
                    return self.execute_statement(stmt, sql, params)
                finally:
                    sched.point_end()
        stmt = parse(sql)
        return self.execute_statement(stmt, sql, params)

    def _try_batched_dml(self, sql: str,
                         params: Optional[list]) -> Optional[ResultSet]:
        """Submit this autocommit point DML to the cross-session write
        batcher.  Returns the scattered result, or None when the session
        must run the sequential path (no plan, batching disabled, window
        closed, singleton group, or group-scope fallback)."""
        sched = getattr(self.instance, "dml_batch_scheduler", None)
        if sched is None or not sched.enabled(self) or not self.schema:
            return None
        schema = self.schema
        p = parameterize(sql)
        pp = self.instance.dml_plans.get((schema.lower(), p.cache_key))
        if pp is None:
            return None
        if pp["schema_version"] != self.instance.catalog.schema_version:
            self.instance.dml_plans.pop((schema.lower(), p.cache_key), None)
            return None
        try:
            vals = p.resolve(params or [])
        except Exception:
            return None
        # same privilege gate the sequential path applies to its AST
        priv = {"insert": "INSERT", "update": "UPDATE",
                "delete": "DELETE"}[pp["kind"]]
        self.instance.privileges.check(self.user, priv,
                                       pp["schema"], pp["table"])
        self._apply_fence()
        t0 = time.time()
        prof = tracing.QueryProfile(
            trace_id=self.instance.trace_ids.next(), sql=sql[:512],
            schema=schema, conn_id=self.conn_id, started_at=t0)
        from galaxysql_tpu.meta.statement_summary import counters_snapshot
        self._ss0 = counters_snapshot(self.instance)
        ticket = self.instance.admission.admit(self, sql)
        try:
            gkey = (schema.lower(), p.cache_key, pp["schema_version"])
            req = sched.submit(gkey, pp, vals, None, prof)
        except Exception:
            ticket.release(error=True)
            raise
        if req is None:
            # sequential fallback: release so the sequential ramp re-admits
            ticket.release()
            return None
        if req.error is not None:
            ticket.release(error=True)
            raise req.error  # isolated to this session; members proceed
        if req.apply_seq:
            self._apply_mark = max(getattr(self, "_apply_mark", 0),
                                   req.apply_seq)
        # the leader bulk-finished profile/ring/metrics at scatter; the woken
        # member's tail is the summary record + admission feedback only
        self.last_trace = prof.trace
        self._summary_record(sql, prof, "TP", "dml_batch", req.affected)
        ticket.release(prof)
        return ok(affected=req.affected)

    _PRIV_BY_STMT = {
        ast.Select: "SELECT", ast.SetOpSelect: "SELECT", ast.Insert: "INSERT",
        ast.Update: "UPDATE", ast.Delete: "DELETE", ast.CreateTable: "CREATE",
        ast.DropTable: "DROP", ast.TruncateTable: "DELETE", ast.AlterTable: "ALTER",
        ast.CreateView: "CREATE", ast.DropView: "DROP",
        ast.CreateIndex: "INDEX", ast.DropIndex: "INDEX", ast.LoadData: "INSERT",
        ast.CreateDatabase: "CREATE", ast.DropDatabase: "DROP",
        ast.CheckTable: "SELECT", ast.FlashbackTable: "CREATE",
        ast.PurgeRecycleBin: "DROP", ast.AdviseIndex: "SELECT",
        ast.Rebalance: "ALTER",
    }

    @staticmethod
    def _stmt_tables(node) -> List[ast.TableName]:
        """Every TableName referenced by a statement (joins, subqueries included)."""
        out: List[ast.TableName] = []
        seen = set()

        def walk(x):
            if id(x) in seen or x is None:
                return
            seen.add(id(x))
            if isinstance(x, ast.TableName):
                out.append(x)
                return
            if isinstance(x, (ast.Node,)) and hasattr(x, "__dataclass_fields__"):
                for f in x.__dataclass_fields__:
                    walk(getattr(x, f))
            elif isinstance(x, (list, tuple)):
                for item in x:
                    walk(item)
        walk(node)
        return out

    def _authorize(self, stmt: ast.Statement):
        pm = self.instance.privileges
        if isinstance(stmt, (ast.CreateUser, ast.DropUser, ast.GrantStmt,
                             ast.RevokeStmt)):
            # account administration requires the super user
            if not pm.is_super(self.user):
                raise errors.AccessDeniedError(
                    f"user administration denied to '{self.user}'")
            return
        priv = self._PRIV_BY_STMT.get(type(stmt))
        if priv is None:
            return
        if isinstance(stmt, (ast.CreateDatabase, ast.DropDatabase)):
            pm.check(self.user, priv, stmt.name)
            return
        tables = self._stmt_tables(stmt)
        if not tables:
            pm.check(self.user, priv, self.schema or "*")
            return
        for t in tables:
            pm.check(self.user, priv, t.schema or self.schema or "*", t.table)

    def execute_statement(self, stmt: ast.Statement, sql: str = "",
                          params: Optional[list] = None) -> ResultSet:
        self._authorize(stmt)
        # kept for remote-DML shipping (the worker re-plans the statement text)
        self._current_sql = sql
        self._current_params = params
        if isinstance(stmt, (ast.Select, ast.SetOpSelect)):
            return self._run_query(stmt, sql, params)
        if isinstance(stmt, (ast.Insert, ast.Update, ast.Delete)):
            return self._run_dml(stmt, sql, params)
        if isinstance(stmt, ast.CreateTable):
            return self._run_create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            return self._run_drop_table(stmt)
        if isinstance(stmt, ast.CreateView):
            return self._run_create_view(stmt)
        if isinstance(stmt, ast.DropView):
            return self._run_drop_view(stmt)
        if isinstance(stmt, ast.TruncateTable):
            return self._run_truncate(stmt)
        if isinstance(stmt, ast.CreateDatabase):
            self.instance.catalog.create_schema(stmt.name, stmt.if_not_exists)
            self.instance.metadb.save_schema(stmt.name)
            return ok()
        if isinstance(stmt, ast.DropDatabase):
            self.instance.recycle.purge_schema(stmt.name)
            self._drop_database(stmt)
            return ok()
        if isinstance(stmt, ast.UseDb):
            self.instance.catalog.schema(stmt.name)  # validates
            self.schema = stmt.name
            return ok()
        if isinstance(stmt, ast.SetStmt):
            return self._run_set(stmt)
        if isinstance(stmt, ast.Show):
            return self._run_show(stmt)
        if isinstance(stmt, ast.Explain):
            return self._run_explain(stmt, params)
        if isinstance(stmt, ast.Describe):
            return self._describe(stmt.table)
        if isinstance(stmt, ast.Begin):
            self._begin()
            return ok()
        if isinstance(stmt, ast.Commit):
            self._commit()
            return ok()
        if isinstance(stmt, ast.Rollback):
            self._rollback()
            return ok()
        if isinstance(stmt, ast.AnalyzeTable):
            return self._run_analyze(stmt)
        if isinstance(stmt, ast.CheckTable):
            return self._run_check_table(stmt)
        if isinstance(stmt, ast.FlashbackTable):
            return self._run_flashback_table(stmt)
        if isinstance(stmt, ast.PurgeRecycleBin):
            return self._run_purge(stmt)
        if isinstance(stmt, ast.AdviseIndex):
            return self._run_advise_index(stmt, params)
        if isinstance(stmt, ast.KillStmt):
            return ok(info="kill acknowledged")
        if isinstance(stmt, ast.CreateCclRule):
            from galaxysql_tpu.utils.ccl import CclRule
            if any(st.rule.name.lower() == stmt.name.lower()
                   for st in GLOBAL_CCL.rules()):
                # silent replacement would zero the live rule's counters and
                # orphan in-flight admissions' slot state — DDL semantics:
                # error unless IF NOT EXISTS asked to keep the existing rule
                if stmt.if_not_exists:
                    return ok()
                raise errors.TddlError(
                    f"CCL rule '{stmt.name}' already exists")
            GLOBAL_CCL.add_rule(CclRule(
                stmt.name, stmt.max_concurrency, stmt.keyword, stmt.user,
                stmt.wait_queue_size, stmt.wait_timeout_ms))
            return ok()
        if isinstance(stmt, ast.DropCclRule):
            if not GLOBAL_CCL.drop_rule(stmt.name) and not stmt.if_exists:
                raise errors.TddlError(f"unknown CCL rule '{stmt.name}'")
            return ok()
        if isinstance(stmt, ast.CreateSlo):
            self.instance.slo.create_sql(stmt)
            return ok()
        if isinstance(stmt, ast.DropSlo):
            self.instance.slo.drop_sql(stmt.name, stmt.if_exists)
            return ok()
        if isinstance(stmt, ast.BaselineStmt):
            return self._run_baseline(stmt)
        if isinstance(stmt, ast.LoadData):
            return self._run_load_data(stmt)
        if isinstance(stmt, ast.CreateUser):
            self.instance.privileges.create_user(stmt.user, stmt.password,
                                                 if_not_exists=stmt.if_not_exists)
            return self._sync_privileges()
        if isinstance(stmt, ast.DropUser):
            self.instance.privileges.drop_user(stmt.user, stmt.if_exists)
            return self._sync_privileges()
        if isinstance(stmt, ast.GrantStmt):
            schema = self._require_schema() if stmt.schema == "" else stmt.schema
            self.instance.privileges.grant(stmt.user, stmt.privileges, schema,
                                           stmt.table)
            return self._sync_privileges()
        if isinstance(stmt, ast.RevokeStmt):
            schema = self._require_schema() if stmt.schema == "" else stmt.schema
            self.instance.privileges.revoke(stmt.user, stmt.privileges, schema,
                                            stmt.table)
            return self._sync_privileges()
        if isinstance(stmt, ast.AlterTable):
            return self._run_alter(stmt, sql)
        if isinstance(stmt, ast.Rebalance):
            return self._run_rebalance(stmt)
        if isinstance(stmt, (ast.CreateIndex, ast.DropIndex)):
            return self._run_index_ddl(stmt, sql)
        raise errors.NotSupportedError(f"statement {type(stmt).__name__}")

    def _run_dml(self, stmt, sql: str, params: Optional[list]) -> ResultSet:
        """Sequential DML ramp: deadline hint, async-apply fencing, admission
        gate, statement-scope MDL, dispatch — and on success, per-digest
        statement-summary attribution (write costs must be as truthful as
        read costs for the admission classifier) plus DML batch-plan
        registration so later plan-identical executions can coalesce."""
        # the MAX_EXECUTION_TIME hint must bind DML too (the SELECT path
        # reads it off the cached plan; DML has no plan cache) — it rides
        # self._deadline into the remote-DML RPC headers
        from galaxysql_tpu.sql.hints import parse_hints
        hint_ms = parse_hints(getattr(stmt, "hints", None)) \
            .get("max_execution_time")
        if hint_ms:
            self._deadline = time.time() + hint_ms / 1000.0
        # statement-scope shared MDL on every referenced table: a
        # repartition cutover cannot swap partition metadata under
        # in-flight DML
        keys = {f"{(t.schema or self._require_schema()).lower()}"
                f".{t.table.lower()}" for t in self._stmt_tables(stmt)}
        # read-your-writes fence (own async-apply watermark), plus a GLOBAL
        # barrier when this DML touches a GSI-bearing table with applies
        # still in flight: a sequential delete racing ahead of a pending
        # async GSI insert would orphan the index row
        self._apply_fence()
        applier = getattr(self.instance, "applier", None)
        if applier is not None and applier.pending():
            try:
                tms = [self.instance.catalog.table(*k.split(".", 1))
                       for k in keys]
            except Exception:
                tms = []
            if any(gsi_targets(self.instance, tm) for tm in tms):
                applier.barrier(self._apply_wait_s())
        t0 = time.time()
        prof = tracing.QueryProfile(
            trace_id=self.instance.trace_ids.next(),
            sql=(sql or "<dml>")[:512], schema=self.schema or "",
            conn_id=self.conn_id, started_at=t0)
        from galaxysql_tpu.meta.statement_summary import counters_snapshot
        self._ss0 = counters_snapshot(self.instance)
        # DML rides the admission gate too (TP class): under overload a
        # write queue must degrade typed, not pile unboundedly onto the
        # store locks
        ticket = self.instance.admission.admit(self, sql or "")
        try:
            with self.instance.mdl.shared(keys):
                if isinstance(stmt, ast.Insert):
                    rs = self._run_insert(stmt, params)
                elif isinstance(stmt, ast.Update):
                    rs = self._run_update(stmt, params)
                else:
                    rs = self._run_delete(stmt, params)
        except Exception:
            ticket.release(error=True)
            raise
        else:
            prof.workload = "TP"
            prof.engine = "dml"
            prof.elapsed_ms = round((time.time() - t0) * 1000, 3)
            # the digest's observed write cost feeds the statement summary +
            # the admission classifier (truthful per-digest costs, PR 10/12)
            self._summary_record(sql, prof, "TP", "dml", rs.affected)
            if self.txn is None:
                from galaxysql_tpu.server import dml_batch
                dml_batch.try_register(self, stmt, sql, params)
            return rs
        finally:
            ticket.release(prof)

    def _apply_wait_s(self) -> float:
        # NOT `ms or default`: a configured 0 means "never wait" (the house
        # 0-as-disable convention), only an absent value takes the default
        ms = self.instance.config.get("APPLY_WAIT_MS", self.vars)
        return (10_000.0 if ms is None else float(ms)) / 1000.0

    def _apply_fence(self):
        """Read-your-writes: wait (bounded) until this session's own async
        GSI/replica applies have landed.  One int compare when idle."""
        mark = getattr(self, "_apply_mark", 0)
        if not mark:
            return
        applier = getattr(self.instance, "applier", None)
        if applier is None:
            self._apply_mark = 0
            return
        if applier.applied_seq < mark:
            applier.wait_applied(mark, self._apply_wait_s())
        self._apply_mark = 0

    def _run_alter(self, stmt: ast.AlterTable, sql: str) -> ResultSet:
        from galaxysql_tpu.ddl.jobs import alter_table_job
        schema = stmt.table.schema or self._require_schema()
        self.instance.catalog.table(schema, stmt.table.table)  # validate early
        if any(a[0] == "repartition" for a in stmt.actions):
            if len(stmt.actions) != 1:
                raise errors.NotSupportedError(
                    "PARTITION BY cannot be combined with other ALTER actions")
            return self._run_repartition(stmt, sql, schema)
        if any(a[0] in ("split_partition", "merge_partitions",
                        "move_partition") for a in stmt.actions):
            if len(stmt.actions) != 1:
                raise errors.NotSupportedError(
                    "SPLIT/MERGE/MOVE PARTITION cannot be combined with "
                    "other ALTER actions")
            return self._run_partition_rebalance(stmt, sql, schema)
        job = alter_table_job(schema, sql, stmt.table.table, stmt.actions)
        self.instance.ddl_engine.submit_and_run(job)
        return ok()

    def _run_repartition(self, stmt: ast.AlterTable, sql: str,
                         schema: str) -> ResultSet:
        """Online repartition: shadow-table backfill + catchup + verify + MDL
        cutover (Balancer.java / RepartitionCutOverTask analog)."""
        from galaxysql_tpu.ddl.repartition import repartition_job
        pd = stmt.actions[0][1]
        cols = []
        for e in pd.exprs:
            if not isinstance(e, ast.Name):
                raise errors.NotSupportedError(
                    "PARTITION BY expression must be a column name")
            cols.append(e.parts[-1])
        tm = self.instance.catalog.table(schema, stmt.table.table)
        for c in cols:
            tm.column(c)  # validates the partition column exists
        method = pd.method if pd.method in ("hash", "key", "range") else "hash"
        count = pd.count or tm.partition.num_partitions or 4
        job = repartition_job(schema, sql, stmt.table.table, method, cols, count)
        self.instance.ddl_engine.submit_and_run(job)
        return ok()

    def _run_partition_rebalance(self, stmt: ast.AlterTable, sql: str,
                                 schema: str) -> ResultSet:
        """Online elastic rebalancing at partition scope: shadow backfill +
        CDC catchup + FastChecker verify + TSO-fenced cutover under the
        exclusive MDL (ddl/rebalance.py; Balancer.java data-movement analog)."""
        from galaxysql_tpu.ddl import rebalance as rb
        action = stmt.actions[0]
        table = stmt.table.table
        if action[0] == "split_partition":
            job = rb.split_partition_job(schema, sql, table, action[1],
                                         into=action[3], at=action[2])
        elif action[0] == "merge_partitions":
            job = rb.merge_partitions_job(schema, sql, table, action[1],
                                          action[2])
        else:
            job = rb.move_partition_job(schema, sql, table, action[1],
                                        action[2])
        self.instance.ddl_engine.submit_and_run(job)
        return ok()

    def _run_rebalance(self, stmt: ast.Rebalance) -> ResultSet:
        """REBALANCE TABLE/DATABASE: one synchronous balancer pass; rows are
        the proposals (and, unless DRY RUN, what happened to the first)."""
        schema = stmt.schema or (None if stmt.table is None
                                 else self._require_schema())
        props = self.instance.balancer.run_once(
            schema, stmt.table, apply=not stmt.dry_run)
        rows = [(p["table"], p["op"], ",".join(str(i) for i in p["pids"]),
                 p.get("group", ""), p["why"],
                 "applied" if p.get("applied") else
                 p.get("error", "proposed"), p.get("job_id") or 0)
                for p in props]
        from galaxysql_tpu.types import datatype as dt
        return ResultSet(
            ["TABLE_NAME", "OP", "PARTITIONS", "TARGET_GROUP", "REASON",
             "STATUS", "JOB_ID"],
            [dt.VARCHAR] * 6 + [dt.BIGINT], rows)

    def _run_index_ddl(self, stmt, sql: str) -> ResultSet:
        from galaxysql_tpu.ddl.jobs import create_index_job, drop_index_job
        schema = stmt.table.schema or self._require_schema()
        if isinstance(stmt, ast.CreateIndex):
            idx = stmt.index
            job = create_index_job(schema, sql,
                                   stmt.table.table,
                                   idx.name or f"i_{idx.columns[0]}", idx.columns,
                                   idx.unique, idx.global_index, idx.covering)
        else:
            job = drop_index_job(schema, sql,
                                 stmt.table.table, stmt.name)
        self.instance.ddl_engine.submit_and_run(job)
        return ok()

    def _run_load_data(self, stmt: ast.LoadData) -> ResultSet:
        """Server-side CSV ingestion (LOAD DATA INFILE; ServerLoadDataHandler analog,
        SURVEY.md App.E).  LOCAL (client-streamed) arrives via the wire layer later."""
        import csv
        schema = stmt.table.schema or self._require_schema()
        tm = self.instance.catalog.table(schema, stmt.table.table)
        store = self.instance.store(tm.schema, tm.name)
        columns = stmt.columns or tm.column_names()
        ts, txn = self._dml_ts()
        total = 0
        batch_size = self.instance.config.get("DML_BATCH_SIZE", self.vars) or 10_000
        delim = stmt.field_terminator.replace("\\t", "\t") or ","
        quote = stmt.enclosed_by or '"'
        try:
            fh = open(stmt.path, newline="")
        except OSError as e:
            raise errors.TddlError(f"Can't read file '{stmt.path}' ({e.strerror})")
        # statement-scope shared MDL like every other DML path: a concurrent
        # ADD/DROP COLUMN swapping partition lanes mid-load is a torn write
        with fh as f, self.instance.mdl.shared(
                {f"{tm.schema.lower()}.{tm.name.lower()}"}):
            reader = csv.reader(f, delimiter=delim, quotechar=quote)
            rows: List[List[Any]] = []
            for i, row in enumerate(reader):
                if i < stmt.ignore_lines:
                    continue
                rows.append([None if v in ("", "\\N") else v for v in row])
                if len(rows) >= batch_size:
                    total += self._load_rows(tm, store, columns, rows, ts, txn)
                    rows = []
            if rows:
                total += self._load_rows(tm, store, columns, rows, ts, txn)
        tm.bump_version()
        self._note_write(tm)
        self.instance.catalog.version += 1
        return ok(affected=total, info=f"Records: {total}")

    def _load_rows(self, tm, store, columns, rows, ts, txn) -> int:
        data = {c: [r[i] if i < len(r) else None for r in rows]
                for i, c in enumerate(columns)}
        data = {tm.column(c).name: vals for c, vals in data.items()}
        with store.append_lock:
            before = [p.num_rows for p in store.partitions]
            n = store.insert_pylists(data, ts)
            ranges = [(pid, before[pid], p.num_rows - before[pid])
                      for pid, p in enumerate(store.partitions)
                      if p.num_rows - before[pid]]
        for pid, start, added in ranges:
            if txn is not None:
                txn.inserted.append((store, pid, start, added))
            self._gsi_write_rows(tm, store, pid, start, added, ts, txn)
        return n

    # -- GSI write maintenance (online index writers, SURVEY.md App.D) -----------
    # Module-level so the async applier (txn/async_apply.py) and the DML
    # batch scheduler (server/dml_batch.py) apply the SAME maintenance the
    # sequential path does; the Session methods delegate.

    def _gsi_targets(self, tm):
        return gsi_targets(self.instance, tm)

    def _gsi_write_rows(self, tm, base_store, pid: int, start: int, n: int,
                        ts: int, txn):
        gsi_write_rows(self.instance, tm, base_store, pid, start, n, ts, txn)

    def _gsi_delete(self, tm, base_store, pid: int, row_ids: np.ndarray,
                    ts: int, txn):
        gsi_delete(self.instance, tm, base_store, pid, row_ids, ts, txn)

    # -- DQL ------------------------------------------------------------------------

    def _require_schema(self) -> str:
        if not self.schema:
            raise errors.TddlError("No database selected")
        return self.schema

    def _snapshot_ts(self) -> int:
        if self.txn is not None:
            return self.txn.snapshot_ts
        return self.instance.tso.next_timestamp()

    def _profiling_enabled(self) -> bool:
        return bool(self.instance.config.get("ENABLE_QUERY_PROFILING",
                                             self.vars))

    def _tracing_enabled(self) -> bool:
        # always-on by default since ISSUE 20 (collection is host-side ramp
        # timestamps only); GALAXYSQL_TRACING=0 env or the param kill it
        return tracing.ALWAYS_ON and bool(
            self.instance.config.get("ENABLE_QUERY_TRACING", self.vars))

    def _digest_of(self, sql: str, schema: str = "") -> str:
        """Statement digest of a raw SQL text (memoized end-to-end: the
        parameterize pass and the hash both cache by exact text)."""
        if not sql or sql.startswith("<"):
            return ""  # internal/synthetic statements have no digest
        from galaxysql_tpu.meta import statement_summary as _ss
        return _ss.digest_key((schema or self.schema or "").lower(),
                              parameterize(sql).parameterized)

    def _summary_record(self, sql: str, prof, workload: str, engine: str,
                        rows: int, plan=None, error: bool = False):
        """Feed the statement-summary store (meta/statement_summary.py) from
        the query exit ramps.  Host-side adds only; the per-query counter
        deltas come from the snapshot _run_query took at entry."""
        if not sql or sql.startswith("<"):
            return
        from galaxysql_tpu.meta import statement_summary as _ss
        ss = self.instance.stmt_summary
        if not ss.on(self.vars):
            return
        p = parameterize(sql)
        if engine in ("point", "batch"):
            fp, orders = "point", ""  # both serve the cached PointPlan shape
        elif engine in ("dml", "dml_batch"):
            fp, orders = "dml", ""  # write statements have no join order
        elif error and plan is None:
            fp, orders = "unknown", ""
        else:
            fp = _ss.plan_fingerprint(plan)
            orders = _ss.encode_orders(getattr(plan, "join_orders", None))
        ss.record(prof.schema, p.parameterized, sql, fp, orders, workload,
                  engine, prof.elapsed_ms, rows,
                  rows_examined=int(getattr(plan, "scanned_rows", 0) or 0),
                  error=error, peak_rss_kb=prof.peak_rss_kb,
                  extras=None if error else
                  _ss.counters_delta(getattr(self, "_ss0", None),
                                     self.instance))

    def _finish_query(self, sql: str, elapsed: float, prof, workload: str,
                      engine: str, rows: int, ctx=None, plan=None):
        """Every query's single exit ramp: fill + record the QueryProfile,
        bump the metrics registry, aggregate into the statement-summary
        store, and apply the slow-SQL gate (the one home for the SLOW_SQL_MS
        check — point, local, and MPP paths all land here)."""
        if FAIL_POINTS.active:
            # SLO-plane burn determinism: inflate the OBSERVED latency of
            # matching queries (no sleeping) so the latency histogram,
            # statement summary, and burn windows all see the storm
            spec = FAIL_POINTS.value(FP_SLO_LATENCY_MS)
            if spec is not None:
                if isinstance(spec, dict):
                    wl_want = str(spec.get("workload", "") or "").upper()
                    sch_want = str(spec.get("schema", "") or "").lower()
                    if (not wl_want or wl_want == (workload or "").upper()) \
                            and (not sch_want or sch_want ==
                                 (prof.schema or "").lower()):
                        elapsed += float(spec.get("ms", 0.0)) / 1000.0
                else:
                    elapsed += float(spec) / 1000.0
        prof.workload = workload
        prof.engine = engine
        prof.rows = rows
        prof.elapsed_ms = round(elapsed * 1000, 3)
        if ctx is not None:
            prof.profiled = bool(getattr(ctx, "collect_stats", False))
            if prof.profiled:
                prof.op_stats = list(ctx.op_stats)
            prof.trace = list(ctx.trace)
        # compile-phase attribution: process-global compile_ms delta across
        # this query (host-side dict reads; retraces are rare steady-state,
        # so the phase usually stays absent)
        c0 = getattr(self, "_compile_ms0", None)
        if c0 is not None:
            from galaxysql_tpu.exec.operators import COMPILE_STATS
            _cms = COMPILE_STATS["compile_ms"] - c0
            if _cms > 0.0:
                prof.phases["compile"] = round(_cms, 3)
        inst = self.instance
        slow_ms = inst.config.get("SLOW_SQL_MS", self.vars)
        # 0 logs every query (MySQL long_query_time=0); negative disables
        is_slow = (slow_ms is not None and slow_ms >= 0
                   and elapsed * 1000 >= slow_ms)
        digest = self._digest_of(sql, prof.schema)
        # tail-sampled retention: the per-query cost is the sampler's one
        # dict probe + one compare (slow/error paths are off the fast path)
        rt = None
        store = getattr(inst, "trace_store", None)
        # cheap-path guard: unsampled healthy queries (prof.spans empty,
        # not slow) never even call offer()
        if store is not None and prof.traced and (prof.spans or is_slow):
            if prof.spans and prof.phases:
                prof.spans[0].attrs["phases"] = dict(prof.phases)
            hint = self._trace_hint
            rt = store.offer(prof, digest, slow=bool(is_slow),
                             forced=bool(hint is not None and hint[3]))
        if prof.profiled or rt is not None:
            # the RSS high-water syscall is ~70us on virtualized kernels —
            # worth it only for profiled or retained queries, never the
            # always-on fast path
            try:
                import resource
                prof.peak_rss_kb = resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss
            except Exception:
                pass  # non-POSIX host: profile lacks the memory datapoint
        inst.profiles.record(prof)
        m = inst.metrics
        # bound metric handles are cached per (workload, engine): name
        # sanitize + registry lookups x4 are measurable at TP serving rates
        lat_h, q_total, q_wl, q_eng = inst.finish_handles(workload, engine)
        lat_h.observe(elapsed * 1000)
        q_total.inc()
        q_wl.inc()
        q_eng.inc()
        tracing.GLOBAL_STATS.bump("queries")
        self._summary_record(sql, prof, workload, engine, rows, plan)
        if is_slow:
            tracing.SLOW_LOG.record(sql or "<stmt>", elapsed, self.conn_id,
                            trace_id=prof.trace_id, workload=workload,
                            digest=digest)
            tracing.GLOBAL_STATS.bump("slow")
            m.counter("slow_queries", "queries over SLOW_SQL_MS").inc()

    def _run_query(self, stmt, sql: str, params: Optional[list]) -> ResultSet:
        schema = self._require_schema()
        _pc = time.perf_counter
        # read-your-writes: this session's own async GSI/replica applies must
        # land before its reads (one int compare when nothing is pending)
        trace_id = self.instance.trace_ids.next()
        # a jax.profiler session is recording: this statement's spans also go
        # into the profiler's trace (utils/tracing.py).  The one check a
        # statement pays while none is.
        annotate = tracing.device_trace_active() and self._tracing_enabled()
        f0 = _pc()
        if annotate:
            if self._trace_hint is not None:
                trace_id = self._trace_hint[0]  # adopted below
            with tracing.phase_annotation("fence_wait", trace_id):
                self._apply_fence()
        else:
            self._apply_fence()
        fence_ms = (_pc() - f0) * 1000.0
        t0 = time.time()
        prof = tracing.QueryProfile(trace_id=trace_id,
                                    sql=(sql or "<stmt>")[:512], schema=schema,
                                    conn_id=self.conn_id, started_at=t0)
        if fence_ms >= 0.05:  # steady state: fence is one int compare
            prof.phases["fence_wait"] = round(fence_ms, 3)
        # statement-summary counter bracket: five host-side reads whose
        # deltas attribute compile/cache/filter/retry work to this digest
        from galaxysql_tpu.meta.statement_summary import counters_snapshot
        self._ss0 = counters_snapshot(self.instance)
        from galaxysql_tpu.exec.operators import COMPILE_STATS
        self._compile_ms0 = COMPILE_STATS["compile_ms"]
        if "information_schema" in (sql or "").lower() or \
                schema.lower() == "information_schema":
            from galaxysql_tpu.server import information_schema
            information_schema.refresh(self.instance, self)
        # trace collection first, so even a shed query leaves a (tiny) tree
        # with its phase attribution behind
        tc = None
        if self._tracing_enabled():
            prof.traced = True
            hint = self._trace_hint
            store = getattr(self.instance, "trace_store", None)
            if hint is not None:
                # adopt the routing tier's trace id: the router pulls this
                # exact id back over the sync wire and grafts our spans
                # under its route span (one trace per cluster path)
                prof.trace_id = hint[0]
                prof.sampled = hint[3]
                full = True  # the router may pull this id on slow/error
            else:
                # the always-on budget: ONE dict probe + ONE compare.
                # Sampled queries build the full span tree; the rest skip
                # the span machinery entirely — if they end slow/shed/
                # errored, the tail ramps synthesize the root span from
                # the profile's phase breakdown
                prof.sampled = store is not None and \
                    store.sampler.decide(self._digest_of(sql, schema))
                # explicit session opt-in (SET ENABLE_QUERY_TRACING=1)
                # always builds the full tree: that's SHOW TRACE debugging;
                # so does a recording profiler session, whose trace would
                # otherwise hold the programs and not the statement
                full = prof.sampled or annotate or \
                    bool(self.vars.get("ENABLE_QUERY_TRACING"))
            if full:
                tc = tracing.TraceContext(prof.trace_id,
                                          node=self.instance.node_id,
                                          annotate=annotate)
                prof.spans = tc.spans  # alias: ring sees spans as they land
                if annotate:
                    self._ann = tc  # the phase ramps below enter spans on it
            else:
                self.last_spans = []
        else:
            self.last_spans = []  # SHOW TRACE must not show a stale tree
        # overload plane first (typed ServerOverloadError shed, lock-free
        # when idle), then the rule-matched CCL gate; both release on the
        # single exit ramp below (idempotent handles — the exception paths
        # may cross release sites)
        ticket = None
        admission = None
        try:
            a0 = _pc()
            try:
                with tc.annotation("phase:admission") if annotate \
                        else _NULL_CTX:
                    ticket = self.instance.admission.admit(self, sql or "")
            finally:
                # shed queries keep their partial attribution: an admission
                # timeout's wait lands in the phases dict BEFORE the typed
                # ServerOverloadError propagates (ISSUE 20 satellite)
                prof.phases["admission"] = round((_pc() - a0) * 1000, 3)
            q0 = _pc()
            try:
                with tc.annotation("phase:queue") if annotate else _NULL_CTX:
                    admission = GLOBAL_CCL.admit(self, sql or "")
            finally:
                prof.phases["queue"] = round((_pc() - q0) * 1000, 3)
            if tc is None:
                return self._run_query_admitted(stmt, sql, params, schema,
                                                t0, prof)
            # manual begin/end + swap_active: the two generator context
            # managers cost ~4us/query — real money on the point path
            root = tc.begin("query", kind="query", sql=prof.sql[:128],
                            conn=self.conn_id, schema=schema)
            prev = tracing.swap_active(tc)
            try:
                rs = self._run_query_admitted(stmt, sql, params, schema,
                                              t0, prof)
            except BaseException as e:
                root.attrs["error"] = f"{type(e).__name__}: {e}"[:256]
                raise
            finally:
                tracing.swap_active(prev)
                tc.end(root)
            self._finish_trace(tc)
            return rs
        except errors.ServerOverloadError as e:
            self._record_query_shed(sql, t0, prof, e, tc)
            raise
        except Exception as e:
            self._record_query_error(sql, t0, prof, e, tc)
            raise
        finally:
            if annotate:
                self._ann = None
            if admission is not None:
                admission.release()
            if ticket is not None:
                ticket.release(prof)

    def _finish_trace(self, tc):
        """Close out a traced query: stamp device telemetry on the root span
        and park the tree for SHOW TRACE."""
        from galaxysql_tpu.exec.device_cache import hbm_high_water
        if tc.spans:
            hbm = hbm_high_water()
            if hbm:
                tc.spans[0].attrs["hbm_peak_bytes"] = hbm
        self.last_spans = list(tc.spans)

    def _record_query_shed(self, sql, t0, prof, exc, tc):
        """Admission shed this query before execution.  No error metrics here
        — the admission plane already counted and published the typed shed —
        but the phase attribution (how long the admission wait burned) and
        the trace skeleton are evidence: tail-retain them so a shed storm is
        diagnosable after the fact."""
        elapsed = time.time() - t0
        prof.elapsed_ms = round(elapsed * 1000, 3)
        prof.error = f"{type(exc).__name__}: {exc}"[:512]
        if tc is not None:
            tc.add("shed", kind="error", parent=tc.root_id,
                   **errors.span_attrs(exc))
            self._finish_trace(tc)
        inst = self.instance
        inst.profiles.record(prof)
        store = getattr(inst, "trace_store", None)
        if store is not None and prof.traced:
            if prof.spans and prof.phases:
                prof.spans[0].attrs["phases"] = dict(prof.phases)
            store.offer(prof, self._digest_of(sql, prof.schema), shed=True)
        self.last_trace = [f"trace-id {prof.trace_id}",
                           f"shed {prof.error}",
                           f"elapsed={elapsed:.3f}s"]

    def _record_query_error(self, sql, t0, prof, exc, tc):
        """A query that dies mid-execution still owes observability its
        elapsed-time attribution: record the profile (with the error), an
        error span closing the trace, and a slow-log entry when the time
        already spent crosses the slow gate — SHOW SLOW and SHOW TRACE must
        explain slow FAILURES, not just slow successes (utils/errors.py
        supplies the errno/sqlstate attributes)."""
        from galaxysql_tpu.utils import errors as _err
        elapsed = time.time() - t0
        prof.elapsed_ms = round(elapsed * 1000, 3)
        prof.error = f"{type(exc).__name__}: {exc}"[:512]
        inst = self.instance
        if tc is not None:
            # the query span has already closed (cursor is back at 0), so
            # parent explicitly under the root — the tree must stay closed
            tc.add("error", kind="error", parent=tc.root_id,
                   **_err.span_attrs(exc))
            self._finish_trace(tc)
        # tail retention: a failed query's trace is ALWAYS kept (timeouts
        # carry the partial phases stamped before the raise)
        store = getattr(inst, "trace_store", None)
        if store is not None and prof.traced:
            if prof.spans and prof.phases:
                prof.spans[0].attrs["phases"] = dict(prof.phases)
            store.offer(prof, self._digest_of(sql, prof.schema))
        inst.profiles.record(prof)
        tracing.GLOBAL_STATS.bump("errors")
        inst.metrics.counter("query_errors",
                             "queries failed mid-execution").inc()
        if isinstance(exc, _err.QueryTimeoutError):
            from galaxysql_tpu.utils.metrics import QUERY_TIMEOUTS
            QUERY_TIMEOUTS.inc()
        # failed queries still owe the digest their error count + elapsed
        self._summary_record(sql, prof, prof.workload or "TP",
                             prof.engine, 0, error=True)
        self.last_trace = [f"trace-id {prof.trace_id}",
                           f"error {prof.error}",
                           f"elapsed={elapsed:.3f}s"]
        slow_ms = inst.config.get("SLOW_SQL_MS", self.vars)
        if slow_ms is not None and slow_ms >= 0 and elapsed * 1000 >= slow_ms:
            tracing.SLOW_LOG.record(sql or "<stmt>", elapsed, self.conn_id,
                            trace_id=prof.trace_id, workload=prof.workload,
                            error=type(exc).__name__,
                            digest=self._digest_of(sql, prof.schema))
            tracing.GLOBAL_STATS.bump("slow")
            inst.metrics.counter("slow_queries",
                                 "queries over SLOW_SQL_MS").inc()

    def _run_query_admitted(self, stmt, sql, params, schema, t0,
                            prof) -> ResultSet:
        if sql and self.instance.point_plans:
            rs = self._try_point_exec(sql, params, schema, t0, prof)
            if rs is not None:
                return rs
        ann = self._ann
        ph = ann.begin("plan", "phase") if ann is not None else None
        p0 = time.perf_counter()
        if sql:
            plan = self.instance.planner.plan_select(sql, schema, params, self)
        else:
            plan = self.instance.planner.bind_statement(stmt, schema, params or [],
                                                        self)
        prof.phases["plan"] = round((time.perf_counter() - p0) * 1000, 3)
        if ph is not None:
            ann.end(ph)
        if stmt is None:
            # SELECT hot path skipped the raw parse; authorize on the plan's
            # (parameterized) AST — same table names, no second parse
            self._authorize(plan.statement)
        cache = None
        if plan.workload == "AP" and self.instance.config.get("ENABLE_TPU_ENGINE",
                                                              self.vars):
            from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE
            cache = GLOBAL_DEVICE_CACHE
        ctx = ExecContext(self.instance.stores, self._snapshot_ts(), params or [],
                          device_cache=cache,
                          txn_id=self.txn.txn_id if self.txn is not None else 0,
                          archive=self.instance.archive,
                          archive_instance=self.instance,
                          hints=getattr(plan, "hints", None))
        ctx.sort_spill_bytes = self.instance.config.get("SORT_SPILL_BYTES",
                                                        self.vars)
        ctx.join_spill_bytes = self.instance.config.get("JOIN_SPILL_BYTES",
                                                        self.vars)
        # resource governance (server/admission.py): a per-query memory-pool
        # child charges hash-join build / agg partial / sort slab bytes
        # against the global hierarchy, and memory-pressure tiers lower the
        # effective spill thresholds so pressured queries trade disk for
        # headroom (NORMAL scale is 1.0 — the steady state pays one compare)
        adm = getattr(self.instance, "admission", None)
        governed = adm is not None and adm.enabled(self, sql or "")
        if governed:
            scale = adm.governor.spill_scale()
            if scale != 1.0:
                ctx.sort_spill_bytes = int(ctx.sort_spill_bytes * scale)
                ctx.join_spill_bytes = int(ctx.join_spill_bytes * scale)
                ctx.agg_spill_bytes = int(ctx.agg_spill_bytes * scale)
        # session-scoped SET ENABLE_SKEW_EXECUTION (the ctx default only sees
        # instance scope)
        from galaxysql_tpu.exec import skew as _skew
        ctx.skew_modes = _skew.exec_modes(ctx.hints, self.instance, self.vars)
        # self-heal pin: plans bound under a live quarantine episode salt the
        # fragment-cache fingerprints so probation and regressed artifacts
        # never cross ('' steady state)
        ctx.plan_pin = getattr(plan, "heal_pin", "")
        # MAX_EXECUTION_TIME deadline: the hint form overrides the session
        # param for this statement (MySQL optimizer-hint semantics)
        hint_ms = getattr(plan, "hints", {}).get("max_execution_time")
        ctx.deadline = t0 + hint_ms / 1000.0 if hint_ms else self._deadline
        # query-scoped runtime statistics: the profile rides the ExecContext so
        # operators, fused segments, and MPP stages all report into it; stats
        # collection (device syncs!) only when profiling is asked for
        ctx.profile = prof
        ctx.collect_stats = self._profiling_enabled()
        if self.txn is not None:
            # the fragment cache bypasses any table this txn has uncommitted
            # writes on (provisional rows are visible to this session only)
            ctx.txn_write_uids = frozenset(
                st.uid for st in self.txn.touched_tables())
            if self.txn.remote:
                ctx.remote_xids = dict(self.txn.remote)
        from galaxysql_tpu.plan import logical as L
        mdl_keys = {f"{n.table.schema.lower()}.{n.table.name.lower()}"
                    for n in L.walk(plan.rel) if isinstance(n, L.Scan)}
        # columnar HTAP routing (storage/columnar.py): large AP scans flip to
        # the CDC-fed replica at a TSO watermark; TP point reads and
        # fresh-read sessions stay on the row store via the fence below
        self._maybe_route_columnar(plan, ctx, sql, schema)
        if governed:
            # created immediately before the try that closes it: an
            # exception between creation and teardown would leak the child
            # onto GLOBAL_POOL.children for the process lifetime
            from galaxysql_tpu.exec.memory import query_pool
            ctx.mem_pool = query_pool(
                self.conn_id,
                int(self.instance.config.get("QUERY_MEM_BYTES", self.vars)
                    or (4 << 30)))
        try:
            with self.instance.mdl.shared(mdl_keys):
                return self._run_query_locked(plan, ctx, sql, t0, prof)
        finally:
            # per-query pool teardown: releases any bytes a failed operator
            # left reserved and unlinks from the global hierarchy
            if ctx.mem_pool is not None:
                ctx.mem_pool.close()

    # -- columnar HTAP routing (storage/columnar.py) ---------------------------

    def _maybe_route_columnar(self, plan, ctx, sql, schema):
        """Route this query's scans onto the columnar replica when every gate
        opens: hatch trio (COLUMNAR hint > ENABLE_COLUMNAR_REPLICA >
        GALAXYSQL_COLUMNAR env), autocommit read (no txn), no flashback, no
        remote tables, the observed/estimated scan size clears
        COLUMNAR_MIN_SCAN_ROWS, every scanned table has a READY replica whose
        schema matches, the read-your-writes fence passes, and the routed
        watermark is inside the COLUMNAR_MAX_LAG_MS freshness SLA.  On route:
        snapshot_ts pins to the watermark and scans read ReplicaView
        snapshots (the fragment cache keys them by replica generation —
        see _fp_scan's "cscan" branch)."""
        from galaxysql_tpu.storage import columnar as _col
        if not _col.ENABLED:
            return
        hint = (ctx.hints or {}).get("columnar")
        if hint == "off":
            return
        mgr = getattr(self.instance, "columnar", None)
        if mgr is None or (hint != "on" and not mgr.enabled(self)):
            return
        if self.txn is not None or ctx.txn_id:
            return  # txn reads must see their own provisional rows
        from galaxysql_tpu.plan import logical as L
        scans = [n for n in L.walk(plan.rel) if isinstance(n, L.Scan)]
        if not scans:
            return
        for n in scans:
            if n.as_of is not None or \
                    getattr(n.table, "remote", None) is not None:
                return  # flashback / plan-shipped scans stay where they are
            if n.point_eq is not None and hint != "on":
                return  # TP index path: the row store's key-Get wins
        if hint != "on" and not self._columnar_signal(sql, schema, scans):
            return
        views = {}
        for n in scans:
            key = f"{n.table.schema.lower()}.{n.table.name.lower()}"
            if key in views:
                continue
            rep = mgr.replica(n.table.schema, n.table.name)
            if hint == "on" and (rep is None or rep.state != _col.READY):
                rep = mgr.ensure_ready(n.table.schema, n.table.name)
            elif rep is None:
                # observed-size signal fired: enroll asynchronously; this
                # query (and every one until READY) stays on the row store
                mgr.request(n.table.schema, n.table.name)
                return
            if rep.sig != tuple(n.table.column_names()):
                return  # DDL outran the tailer; reseed pending
            view = rep.view()
            if view is None:
                return
            views[key] = view
        # one snapshot timestamp for the whole query: the minimum watermark.
        # Every view serves any ts in [seed_ts, its watermark], so min(W) is
        # exact everywhere — unless a fresh seed starts above it.
        w = min(v.watermark for v in views.values())
        if w <= 0 or w < max(v.seed_ts for v in views.values()):
            return
        if getattr(self, "_last_commit_ts", 0) > w:
            return  # read-your-writes fence: this session wrote past W
        if hint != "on":
            from galaxysql_tpu.meta.tso import LOGICAL_BITS
            max_lag = float(self.instance.config.get(
                "COLUMNAR_MAX_LAG_MS", self.vars) or 10_000)
            if time.time() * 1000.0 - (w >> LOGICAL_BITS) > max_lag:
                return  # freshness SLA blown: fall back to the row store
        ctx.snapshot_ts = w
        ctx.columnar = views
        mgr.routed.inc()

    def _columnar_signal(self, sql, schema, scans) -> bool:
        """Is this statement big enough for the replica?  Primary signal:
        the statement summary's observed per-digest rows-examined (PR 10's
        runtime truth); cold digests fall back to the planner's estimate."""
        min_rows = int(self.instance.config.get(
            "COLUMNAR_MIN_SCAN_ROWS", self.vars) or 50_000)
        if sql and not sql.startswith("<"):
            try:
                execs, avg_rx = self.instance.stmt_summary.digest_signal(
                    (schema or self.schema or "").lower(),
                    parameterize(sql).parameterized)
            except Exception:  # galaxylint: disable=swallow -- the size signal is advisory: a summary fault must never fail a query, it only defers to the estimate below
                execs, avg_rx = 0, 0.0
            if execs > 0:
                return avg_rx >= min_rows
        from galaxysql_tpu.plan.rules import estimate_rows
        est = 0
        for n in scans:
            try:
                est += int(estimate_rows(n) or 0)
            except Exception:  # galaxylint: disable=swallow -- estimate faults defer to "too small": mis-estimating must never fail a query
                pass
        return est >= min_rows

    # -- point-plan fast path (DirectShardingKeyTableOperation / XPlan key-Get
    # analog, Planner.java:914): archetypal `SELECT cols FROM t WHERE pk = ?`
    # statements skip binder+optimizer entirely on re-execution — the cached
    # PointPlan routes to the owning partition and reads index candidates.

    def _register_point_plan(self, plan, batch):
        from galaxysql_tpu.expr import ir as _ir
        from galaxysql_tpu.plan import logical as L
        from galaxysql_tpu.plan.rules import _col_lit_cmp
        if plan.spm_key is None or plan.param_count != 1 or \
                getattr(plan, "hints", None):
            return
        rel = plan.rel
        proj = rel if isinstance(rel, L.Project) else None
        inner = proj.child if proj is not None else rel
        if not (isinstance(inner, L.Filter) and isinstance(inner.child, L.Scan)):
            return
        scan = inner.child
        if scan.point_eq is None or scan.as_of is not None or \
                getattr(scan.table, "remote", None) is not None:
            return
        cond = inner.cond
        if not (isinstance(cond, _ir.Call) and cond.op == "eq"):
            return
        cl = _col_lit_cmp(cond)
        if cl is None:
            return
        col, lit, _flip = cl
        id_to_col = {oid: c for oid, c in scan.columns}
        if id_to_col.get(col.name, "").lower() != scan.point_eq[0].lower():
            return
        bound = getattr(plan, "bound_params", None)
        b0 = bound[0] if bound else None
        if isinstance(b0, DecimalParam):
            b0 = b0.value
        if not bound or lit.value != b0:
            return  # the one param must BE the point key value
        out = []
        if proj is not None:
            for name, e in proj.exprs:
                if not isinstance(e, _ir.ColRef) or e.name not in id_to_col:
                    return
                out.append(id_to_col[e.name])
        else:
            out = [c for _, c in scan.columns]
        tm = scan.table
        fields = plan.fields()
        pp = {
            "schema": tm.schema, "table": tm.name,
            "key_col": scan.point_eq[0], "out_cols": out,
            "names": list(plan.display_names),
            "types": [t for _, t, _ in fields],
            "schema_version": self.instance.catalog.schema_version,
        }
        if len(self.instance.point_plans) > 512:
            self.instance.point_plans.clear()
        self.instance.point_plans[plan.spm_key] = pp

    def _try_point_exec(self, sql, params, schema, t0, prof):
        p = parameterize(sql)
        pp = self.instance.point_plans.get((schema.lower(), p.cache_key))
        if pp is None:
            return None
        if pp["schema_version"] != self.instance.catalog.schema_version:
            self.instance.point_plans.pop((schema.lower(), p.cache_key), None)
            return None
        sched = getattr(self.instance, "batch_scheduler", None)
        if sched is None:
            return self._point_exec(pp, p, sql, params, schema, t0, prof)
        # bracket the WHOLE point path (batched or sequential): the batch
        # scheduler's adaptive window keys off live point-query concurrency
        sched.point_begin()
        try:
            return self._point_exec(pp, p, sql, params, schema, t0, prof)
        finally:
            sched.point_end()

    def _point_exec(self, pp, p, sql, params, schema, t0, prof):
        vals = p.resolve(params or [])
        if len(vals) != 1:
            return None
        # same privilege gate the planned path applies to its statement AST
        self.instance.privileges.check(self.user, "SELECT",
                                       pp["schema"], pp["table"])
        value = vals[0]
        if isinstance(value, DecimalParam):
            value = value.value
        try:
            tm = self.instance.catalog.table(pp["schema"], pp["table"])
            store = self.instance.store(pp["schema"], pp["table"])
        except Exception:
            return None
        inst_key = f"{tm.schema.lower()}.{tm.name.lower()}"
        if self.instance.archive.files_for(inst_key, None):
            return None  # cold rows live outside the index: full path
        key_col = pp["key_col"]
        ann = self._ann
        ph = ann.begin("execute", "phase") if ann is not None else None
        x0 = time.perf_counter()
        if value is None:
            rows = []  # eq NULL matches nothing
        else:
            from galaxysql_tpu.plan.rules import _lane_encode
            lane_val = _lane_encode(tm, key_col, value)
            if lane_val is None:
                if ph is not None:
                    ann.end(ph)
                return None
            # cross-session batching: coalesce with other sessions executing
            # this same parameterized statement (returns None -> run solo)
            brs = self._try_batched_point(pp, p, lane_val, sql, t0, prof,
                                          schema)
            if brs is not None:
                if ph is not None:
                    ann.end(ph)
                return brs
            from galaxysql_tpu.meta.catalog import PartitionRouter
            # route in LANE domain: hash routing on insert keys off the lane
            # values (dictionary codes for strings, scaled ints for decimals).
            # int() matches route_rows' astype(int64) truncation of float
            # lanes, so a float key routes to the same shard it was written to
            pids = PartitionRouter(tm).prune_eq(key_col, int(lane_val))
            if pids is None:
                pids = range(len(store.partitions))
            snap = self._snapshot_ts()
            txn_id = self.txn.txn_id if self.txn is not None else 0
            from galaxysql_tpu import native
            rows = []
            with self.instance.mdl.shared({inst_key}):
                for pid in pids:
                    part = store.partitions[pid]
                    if part.num_rows == 0:
                        continue
                    with part.lock:
                        ids = part.key_candidates(key_col, lane_val)
                        if ids.size == 0:
                            continue
                        keep = part.valid[key_col][ids] & native.visible_mask(
                            part.begin_ts[ids], part.end_ts[ids], snap, txn_id)
                        ids = ids[keep]
                        if ids.size == 0:
                            continue
                        from galaxysql_tpu.chunk.batch import Column
                        out_cols = []
                        for cname, typ in zip(pp["out_cols"], pp["types"]):
                            c = Column(part.lanes[cname][ids],
                                       part.valid[cname][ids],
                                       tm.column(cname).dtype,
                                       tm.dictionaries.get(cname.lower()))
                            out_cols.append(c.to_pylist())
                    rows.extend(zip(*out_cols))
        prof.phases["execute"] = round((time.perf_counter() - x0) * 1000, 3)
        if ph is not None:
            ann.end(ph)
        elapsed = time.time() - t0
        self.last_trace = [f"trace-id {prof.trace_id}",
                           f"point-plan {pp['table']}.{key_col}",
                           f"elapsed={elapsed:.3f}s workload=TP"]
        prof.trace = list(self.last_trace)
        self._finish_query(sql, elapsed, prof, "TP", "point", len(rows))
        self.instance.counters.inc("point_plan_queries")
        return ResultSet(pp["names"], pp["types"], rows)

    def _try_batched_point(self, pp, psql, lane_val, sql, t0,
                           prof, schema) -> Optional[ResultSet]:
        """Submit this point read to the cross-session batch scheduler
        (server/batch_scheduler.py).  Returns the scattered ResultSet, or
        None when the session must run the sequential path itself: batching
        disabled, arrival rate too low (window 0), singleton group, or a
        group-scope fallback.

        Snapshot semantics: a transaction holding ANY writes bypasses —
        its provisional (-txn_id) stamps need own-txn visibility the shared
        group program must not apply to other members.  A read-only
        transaction groups only with sessions pinned to the SAME snapshot
        (pinned_ts rides the group key); autocommit sessions share one
        flush-time TSO."""
        sched = getattr(self.instance, "batch_scheduler", None)
        if sched is None or not sched.enabled(self):
            return None
        pinned = None
        if self.txn is not None:
            if self.txn.inserted or self.txn.deleted or self.txn.remote:
                return None  # own-txn writes: sequential own-visibility path
            pinned = self.txn.snapshot_ts
        gkey = (schema.lower(), psql.cache_key, pinned, pp["schema_version"])
        req = sched.submit(gkey, pp, lane_val, pinned, prof)
        if req is None:
            return None
        if req.error is not None:
            raise req.error  # isolated to this session; group members proceed
        # the leader bulk-finished profile/ring/metrics at scatter
        # (BatchScheduler._bulk_finish): the woken member's serialized tail
        # is only SHOW TRACE state, the statement-summary record, the
        # per-session slow-SQL gate, and the ResultSet handover (req.rows is
        # this request's own scatter slice)
        self.last_trace = prof.trace
        self._summary_record(sql, prof, "TP", "batch", len(req.rows))
        slow_ms = self.instance.config.get("SLOW_SQL_MS", self.vars)
        if slow_ms is not None and slow_ms >= 0:
            elapsed = time.time() - t0
            if elapsed * 1000 >= slow_ms:
                tracing.SLOW_LOG.record(sql, elapsed, self.conn_id,
                                        trace_id=prof.trace_id, workload="TP",
                                        digest=self._digest_of(sql, schema))
                tracing.GLOBAL_STATS.bump("slow")
                self.instance.metrics.counter(
                    "slow_queries", "queries over SLOW_SQL_MS").inc()
        return ResultSet(pp["names"], pp["types"], req.rows)

    def _try_mpp(self, plan, ctx, count: bool):
        """Engine dispatch shared by real execution and EXPLAIN ANALYZE
        (which must report the engine users actually run): the MPP result
        batch, or None for the local engine.  `count` bumps the
        mpp_queries/mpp_fallback_local counters (real executions only —
        EXPLAIN ANALYZE must not skew the engine ratios)."""
        engine_hint = getattr(plan, "hints", {}).get("engine")
        want_mpp = engine_hint == "MPP" or (
            engine_hint is None and plan.workload == "AP" and
            self.instance.config.get("ENABLE_MPP", self.vars) and
            plan.scanned_rows >= self.instance.config.get("MPP_MIN_AP_ROWS",
                                                          self.vars))
        if not want_mpp:
            return None
        # cluster MPP mode: the plan compiles to SPMD stages over the
        # device mesh (ExecutorHelper.executeCluster analog)
        mesh = self.instance.mesh()
        if mesh is None:
            return None
        from galaxysql_tpu.parallel.mpp import MppExecutor
        try:
            batch = MppExecutor(ctx, mesh).execute(plan.rel)
            if count:
                self.instance.counters.inc("mpp_queries")
            return batch
        except (errors.NotSupportedError,
                errors.WorkerUnavailableError) as e:
            # plan shape not yet distributed, or a worker died
            # mid-MPP: local engine — NEVER silent (trace tag +
            # information_schema.engine_counters).  Data permits
            # by construction: MPP stages only read local stores
            # (remote scans raise NotSupportedError at planning).
            if count:
                self.instance.counters.inc("mpp_fallback_local")
            ctx.trace.append(f"mpp-fallback {e}")
            # fresh runtime-filter hub: the aborted MPP walk may
            # have consumed scan edges the local run must re-wire
            from galaxysql_tpu.exec.runtime_filter import \
                RuntimeFilterManager
            ctx.rf = RuntimeFilterManager(
                hints=ctx.hints, metrics=self.instance.metrics)
            return None

    def _run_query_locked(self, plan, ctx, sql, t0, prof) -> ResultSet:
        from galaxysql_tpu.utils.tracing import SEGMENT_TRACER
        # segment spans correlate to THIS query's profile (not the global
        # ring) — bound only when profiling, since spans cost a device sync
        span_scope = SEGMENT_TRACER.scoped(prof.segments) \
            if ctx.collect_stats else contextlib.nullcontext()
        engine_hint = getattr(plan, "hints", {}).get("engine")
        ann = self._ann
        ph = ann.begin("execute", "phase") if ann is not None else None
        x0 = time.perf_counter()
        with span_scope:
            batch = self._try_mpp(plan, ctx, count=True)
            mpp_used = batch is not None
            if batch is None:
                op = build_operator(plan.rel, ctx)
                # TP fast path: pin execution to the host CPU backend — point
                # queries must not pay accelerator dispatch/compile latency
                # (CURSOR-mode bypass, SURVEY.md §7.3 'latency floor')
                device_ctx = _cpu_device_ctx() \
                    if (plan.workload == "TP" or engine_hint == "TP") else _NULL_CTX
                with device_ctx:
                    # SHOW TRACE names where this statement's programs ran
                    # (the accelerator, or the CPU device under the TP pin)
                    ctx.trace.append(f"exec-device {runtime.exec_device()}")
                    if ann is None:
                        batch = run_to_batch(op)
                    else:
                        # the result's lanes come to the host one D2H read
                        # each: a span of its own in the profiler's trace
                        parts = list(op.batches())
                        with ann.annotation("transfer:result"):
                            batch = concat_batches(parts)
        prof.phases["execute"] = round((time.perf_counter() - x0) * 1000, 3)
        if ph is not None:
            ann.end(ph)
            ph = ann.begin("serialize", "phase")
        s0 = time.perf_counter()
        batch = batch.compact()
        rows = batch.to_pylist()
        prof.phases["serialize"] = round((time.perf_counter() - s0) * 1000, 3)
        if ph is not None:
            ann.end(ph)
        fields = plan.fields()
        if plan.workload == "TP":
            self._register_point_plan(plan, batch)
        elapsed = time.time() - t0
        if getattr(plan, "spm_key", None) is not None:
            # during PROBATION this execution is a heal verification sample;
            # a filled sample quota returns the episode's verdict (None on
            # the steady-state path — one extra attribute compare).  Heal
            # bookkeeping must never fail the user query riding this ramp:
            # the result set is already computed.
            try:
                heal_verdict = self.instance.planner.spm.record_execution(
                    plan.spm_key, elapsed * 1000.0,
                    getattr(plan, "bound_params", None),
                    orders=plan.join_orders,
                    stats_version=self.instance.catalog.stats_version)
                if heal_verdict is not None:
                    self.instance.stmt_summary.apply_heal_verdict(
                        heal_verdict)
            except Exception as heal_exc:  # pragma: no cover - defensive
                try:
                    from galaxysql_tpu.utils import events as _events
                    self.instance.stmt_summary.heal_failures.inc()
                    self.instance.planner.spm.abort_heal(
                        plan.spm_key, f"verdict error {heal_exc!r}")
                    _events.publish(
                        "plan_heal_failed",
                        f"heal verdict error {heal_exc!r}",
                        node=self.instance.node_id, reason="internal_error")
                except Exception:
                    pass
        self.last_trace = [f"trace-id {prof.trace_id}"] + ctx.trace + \
            [f"elapsed={elapsed:.3f}s workload={plan.workload}"]
        self._finish_query(sql, elapsed, prof, plan.workload,
                           "mpp" if mpp_used else "local", len(rows), ctx,
                           plan=plan)
        return ResultSet(plan.display_names, [t for _, t, _ in fields], rows,
                         batch=batch)

    # -- DML -------------------------------------------------------------------------

    def _begin(self):
        if self.txn is None:
            self.txn = Transaction(self.instance.tso.next_timestamp())

    def _commit(self):
        txn = self.txn
        self.txn = None
        if txn is None:
            return
        try:
            self._commit_txn(txn)
        finally:
            # post-outcome epoch bump for worker-resident tables this txn
            # wrote: whatever peers cached between the statement-time bump
            # and the commit apply is invalidated now that the outcome holds
            for sch, tbl in txn.remote_tables:
                self._note_remote_write(sch, tbl)

    def _commit_txn(self, txn):
        policy = str(self.instance.config.get("TRANSACTION_POLICY", self.vars))
        if policy.upper() == "XA" or txn.remote:
            # two-phase commit across the touched stores (+ worker branches),
            # with a logged commit point and recovery (TsoTransaction 2PC
            # analog, SURVEY.md §3.4) — a txn spanning a worker ALWAYS takes
            # this path regardless of policy: its branches need the protocol
            from galaxysql_tpu.txn.xa import TwoPhaseCoordinator
            coord = self.instance.xa_coordinator
            try:
                cts = coord.commit(txn)
            except errors.TransactionError as e:
                cts = getattr(e, "commit_ts", None)
                if cts is not None:
                    # committed with in-doubt branches: the outcome is decided,
                    # so the binlog must still record it at the commit ts
                    self.instance.cdc.flush_txn(txn, cts)
                    if txn.inserted or txn.deleted:
                        self.instance.catalog.version += 1
                raise
            self.instance.cdc.flush_txn(txn, cts)
            if txn.inserted or txn.deleted:
                self.instance.catalog.version += 1
            self._last_commit_ts = cts
            return
        # stamp via the XA participant helper (single home for the commit/rollback
        # stamping invariants; bump_version per store included).  The commit point
        # is logged FIRST: a crash mid-stamping would otherwise be resolved by
        # boot recovery as presumed-abort on the not-yet-stamped stores only —
        # a half-committed txn (base table vs GSI diverging).  TSO fetch +
        # commit-point fsync ride the group-commit gate, amortized across
        # concurrent committers (txn/xa.GroupCommitGate).
        from galaxysql_tpu.txn.xa import participants_of
        parts = participants_of(txn)
        gate = self.instance.xa_coordinator.group_gate
        if parts:
            commit_ts = gate.commit_point(txn.txn_id)
            for sp in parts:
                sp.commit(commit_ts)
            gate.log_state(txn.txn_id, "DONE", commit_ts)
        else:
            commit_ts = self.instance.tso.next_timestamp()
        self.instance.cdc.flush_txn(txn, commit_ts)
        if txn.inserted or txn.deleted:
            self.instance.catalog.version += 1
        self._last_commit_ts = commit_ts

    def _rollback(self):
        txn = self.txn
        self.txn = None
        if txn is None:
            return
        for sch, tbl in txn.remote_tables:
            self._note_remote_write(sch, tbl)
        # undo via the XA participant helper: stamps own appended rows permanently
        # dead and restores provisional delete stamps — lanes never shrink (see
        # StoreParticipant.rollback for the concurrent-writer invariant)
        from galaxysql_tpu.txn.xa import participants_of, remote_participants_of
        for sp in participants_of(txn):
            sp.rollback()
        for rp in remote_participants_of(self.instance, txn):
            rp.rollback()

    def _dml_ts(self) -> Tuple[int, Optional[Transaction]]:
        """Timestamp to stamp writes with: provisional (-txn_id) inside a transaction,
        a real TSO value for autocommit single-statement writes."""
        if self.txn is not None:
            return -self.txn.txn_id, self.txn
        ts = self.instance.tso.next_timestamp()
        # read-your-writes fence for the columnar router: a later scan must
        # not route to a replica watermark below this write (txn commits
        # stamp the same field in _commit)
        self._last_commit_ts = ts
        return ts, None

    def _note_write(self, tm):
        """Post-DML fragment-cache hygiene: the version bump already makes
        stale fingerprints unreachable; this frees their bytes immediately.
        GSI stores took the same write but autocommit statements have no
        commit-time participant bump for them — bump here so version-keyed
        caches (fragment, device lanes) never serve a stale covering-index
        scan."""
        metas = [tm]
        try:
            for _i, gtm, _gstore in self._gsi_targets(tm):
                gtm.bump_version()
                metas.append(gtm)
        except Exception:
            pass  # virtual/remote tables without index stores
        fcache = getattr(self.instance, "frag_cache", None)
        if fcache is not None:
            for t in metas:
                fcache.invalidate_table(f"{t.schema.lower()}.{t.name.lower()}")

    def _run_insert(self, stmt: ast.Insert, params: Optional[list]) -> ResultSet:
        schema = self._require_schema()
        tname = stmt.table.table
        tm = self.instance.catalog.table(stmt.table.schema or schema, tname)
        rrs = self._remote_dml(tm)
        if rrs is not None:
            return rrs
        store = self.instance.store(tm.schema, tm.name)
        ts, txn = self._dml_ts()

        if stmt.select is not None:
            sub = self._run_query(stmt.select, "", params)
            columns = stmt.columns or tm.column_names()
            data = {c: [r[i] for r in sub.rows] for i, c in enumerate(columns)}
        else:
            columns = stmt.columns or tm.column_names()
            binder = Binder(self.instance.catalog, schema, params or [])
            scope = Scope()
            data: Dict[str, List[Any]] = {c: [] for c in columns}
            for row in stmt.rows:
                if len(row) != len(columns):
                    raise errors.TddlError("Column count doesn't match value count")
                for c, v in zip(columns, row):
                    e = binder._bind_expr(v, scope)
                    if not isinstance(e, ir.Literal):
                        e = _fold_constant(e)
                    data[c].append(e.value)
        # normalize column name case
        data = {tm.column(c).name: vals for c, vals in data.items()}
        # append_lock: the appended-range derivation must not interleave
        # with a concurrent writer's appends (see TableStore.append_lock)
        store._lockdep_probe()  # FP_LOCK_INVERT only; disarmed = one bool
        with store.append_lock:
            before_counts = [p.num_rows for p in store.partitions]
            n = store.insert_pylists(data, ts)
            ranges = [(pid, before_counts[pid],
                       p.num_rows - before_counts[pid])
                      for pid, p in enumerate(store.partitions)
                      if p.num_rows - before_counts[pid]]
        for pid, start, added in ranges:
            if txn is not None:
                txn.inserted.append((store, pid, start, added))
            self._gsi_write_rows(tm, store, pid, start, added, ts, txn)
            self.instance.cdc.capture_range(tm, store, pid, start, added,
                                            ts, txn, self)
        tm.bump_version()
        self._note_write(tm)
        self.instance.catalog.version += 1
        return ok(affected=n)

    def _remote_dml(self, tm) -> Optional[ResultSet]:
        """DML on a worker-resident table: ship the statement to the owning
        worker inside a distributed-txn branch (MyJdbcHandler.java:136 physical
        DML execution; the branch is committed by the XA coordinator with the
        local stores as co-participants)."""
        if getattr(tm, "remote", None) is None:
            return None
        primary = (tm.remote["host"], tm.remote["port"])
        if self.instance.workers.get(primary) is None:
            raise errors.TddlError(
                f"remote table {tm.name}: no worker attached")
        if self.instance.ha.worker_fenced(primary) and \
                not self.instance.try_revive_worker(primary):
            raise errors.WorkerUnavailableError(
                f"remote table {tm.name}: worker {primary[0]}:{primary[1]} "
                "is fenced", sent=False)
        # synchronous replication: the statement ships to the primary AND every
        # live replica as branches of the same distributed txn; a fenced
        # replica is marked stale and excluded from read routing until rebuilt
        endpoints = [primary]
        for r in tm.replicas:
            a = (r["host"], r["port"])
            if r.get("stale") or a not in self.instance.workers:
                continue
            if self.instance.ha.worker_fenced(a):
                r["stale"] = True
                continue
            endpoints.append(a)
        auto = self.txn is None
        # ASYNC replica legs (autocommit only): the statement commits after
        # the PRIMARY applied; replica branches ship from the background
        # applier, batched per endpoint and uid-stamped so the worker dedupe
        # window makes retries exactly-once (PR 8).  The session fences its
        # own subsequent reads on the apply watermark; a replica that still
        # fails goes STALE, the synchronous path's contract applied late.
        applier = getattr(self.instance, "applier", None)
        async_rep = (auto and applier is not None and len(endpoints) > 1 and
                     bool(self.instance.config.get("ENABLE_ASYNC_APPLY",
                                                   self.vars)))
        rep_addrs = []
        if async_rep:
            rep_addrs = endpoints[1:]
            endpoints = [primary]
        self._begin()
        affected = 0
        # idempotency token: the coordinator stamps one statement uid; the
        # worker's dedupe window replays the recorded result on a reconnect
        # retry, so the retry policy may re-send DML without double-applying
        # (each endpoint keeps its own window, so one uid serves them all)
        stmt_uid = f"{self.instance.node_id}:{self.instance.trace_ids.next()}"
        for addr in endpoints:
            had_branch = addr in self.txn.remote
            xid = self.txn.remote.setdefault(addr, f"g{self.txn.txn_id}")
            try:
                # only the PRIMARY rpc carries the statement deadline: once
                # the primary applied, the statement is on its committed
                # course and every replica must receive it (or be marked
                # stale) — a statement-deadline kill between endpoints would
                # leave a non-stale replica silently missing a write the txn
                # later commits.  Replica legs still get a FIXED bound: a
                # hung replica costs seconds (then goes stale), not the full
                # socket timeout times the retry budget.
                leg_deadline = self._deadline if addr == primary \
                    else time.time() + self.REPLICA_DML_TIMEOUT_S
                resp, _ = self.instance.workers[addr].request({
                    "op": "dml", "xid": xid, "schema": tm.schema,
                    "sql": self._current_sql, "uid": stmt_uid,
                    "params": list(self._current_params or [])},
                    deadline=leg_deadline)
                # request() raises on any error response, so reaching here
                # means the statement APPLIED; worker-reported errors arrive
                # via the except-TddlError branch below
                err = None
                ambiguous = False
                reached = True
            except errors.QueryTimeoutError as e:
                if addr != primary:
                    # a replica leg's BOUNDED wait tripped (hung replica):
                    # same contract as any replica failure — mark it stale
                    # below and let the statement succeed on the primary
                    err = str(e)
                    ambiguous = False
                    reached = True
                else:
                    # A POST-send primary timeout means the branch outcome
                    # is UNKNOWN — the write may have applied before the
                    # reply was lost — so the only divergence-free answer is
                    # to roll the transaction back (xa_rollback undoes an
                    # applied-but-unacked branch write); and the client MUST
                    # hear that the txn died (a statement-scoped 3024 would
                    # let it "COMMIT" a rolled-back txn, silently losing
                    # every other statement).  A PRE-send timeout provably
                    # applied nothing: statement-scoped, the txn survives.
                    from galaxysql_tpu.utils.metrics import QUERY_TIMEOUTS
                    QUERY_TIMEOUTS.inc()  # DML kills count too, not just DQL
                    if auto:
                        self._rollback()
                        raise
                    if getattr(e, "sent", True):
                        self._rollback()
                        raise errors.TransactionError(
                            f"query deadline exceeded with unknown branch "
                            f"outcome; transaction rolled back: {e}")
                    if not had_branch:
                        self.txn.remote.pop(addr, None)  # never opened
                    raise
            except errors.ProtocolError as e:
                # a corrupt REPLY frame means the worker executed and the
                # outcome is unknown; an OUTBOUND validation failure
                # (_gx_sent False: the frame never shipped) provably applied
                # nothing and stays statement-scoped
                err = str(e)
                reached = bool(getattr(e, "_gx_sent", True))
                ambiguous = reached
            except (errors.WorkerUnavailableError, ConnectionError,
                    OSError) as e:
                err = str(e)
                # transport-level death: ambiguous ONLY if bytes may have
                # reached the worker (the write may have applied before the
                # reply was lost).  A breaker fast-fail / connect-refused
                # failure (sent=False) provably applied nothing — the txn
                # can keep statement-scoped semantics.
                reached = bool(getattr(e, "sent", True))
                ambiguous = reached
            except errors.TddlError as e:
                # worker-REPORTED error (request() raises these from the
                # resp error field): the statement failed engine-side,
                # nothing applied — outcome is KNOWN (the worker-side branch
                # session exists, so its registration must stay)
                err = str(e)
                ambiguous = False
                reached = True
            if err:
                if addr != primary:
                    # a failed REPLICA write must not diverge silently: drop
                    # its branch, mark it stale (excluded from reads until
                    # rebuilt), and let the statement succeed on the primary
                    for r in tm.replicas:
                        if (r["host"], r["port"]) == addr:
                            r["stale"] = True
                    self.txn.remote.pop(addr, None)
                    try:
                        # bounded: a HUNG replica must not stall the
                        # statement on its own cleanup (the branch resolves
                        # via xa_recover when the replica returns)
                        self.instance.workers[addr].request(
                            {"op": "xa_rollback", "xid": xid},
                            deadline=time.time() + 5.0)
                    except Exception as cex:
                        # the stale-mark above already fences the replica;
                        # journal the stranded branch so operators see WHY
                        # xa_recover has work (lint: typed-error discipline)
                        from galaxysql_tpu.utils import events
                        events.publish(
                            "replica_cleanup_failed",
                            f"replica rollback for {xid} at {addr} failed "
                            f"({type(cex).__name__}); branch resolves via "
                            f"xa_recover", severity="warn",
                            node=self.instance.node_id,
                            dedupe=f"dml-rb:{addr}")
                    continue
                if auto:
                    self._rollback()
                    raise errors.TddlError(f"worker DML failed: {err}")
                if ambiguous:
                    # an AMBIGUOUS primary failure aborts even an explicit
                    # txn: the branch may hold the write, and a later COMMIT
                    # would persist a statement the client was told failed.
                    # A worker-reported error instead keeps MySQL
                    # statement-scoped semantics (nothing applied; the txn
                    # survives).
                    self._rollback()
                    raise errors.TransactionError(
                        f"worker DML failed with unknown outcome; "
                        f"transaction rolled back: {err}")
                if not reached and not had_branch:
                    # nothing ever hit the wire AND this statement was the
                    # branch's registrar: unregister it, or the surviving
                    # txn's COMMIT would prepare a branch the worker never
                    # opened ("unknown branch" -> spurious full rollback)
                    self.txn.remote.pop(addr, None)
                raise errors.TddlError(f"worker DML failed: {err}")
            if addr == primary:
                affected = int(resp.get("affected", 0))
        # remote tables have no CN-side version: bump the local fragment
        # epoch and ride the SyncBus so every attached node (workers, peer
        # coordinators via Instance.sync_peer) drops its cached fragments —
        # the cross-coordinator invalidation plane.  The statement-time bump
        # covers long transactions; _commit/_rollback bump AGAIN once the
        # outcome is applied, closing the window where a peer re-caches
        # pre-commit worker state under the new epoch.
        self.txn.remote_tables.add((tm.schema, tm.name))
        self._note_remote_write(tm.schema, tm.name)
        if auto:
            self._commit()
            if rep_addrs:
                cts = getattr(self, "_last_commit_ts", 0)
                mark = applier.enqueue([
                    {"kind": "replica", "addr": a, "schema": tm.schema,
                     "sql": self._current_sql,
                     "params": list(self._current_params or []),
                     "uid": f"{stmt_uid}:r{ai}", "commit_ts": cts,
                     "timeout_s": self.REPLICA_DML_TIMEOUT_S,
                     "base_schema": tm.schema, "base_table": tm.name}
                    for ai, a in enumerate(rep_addrs)])
                self._apply_mark = max(getattr(self, "_apply_mark", 0), mark)
        return ok(affected=affected)

    def _sync_privileges(self) -> ResultSet:
        """After any user/grant mutation: peer coordinators share the metadb
        but keep their own privilege decision caches — broadcast the drop
        (workers ignore the action; best-effort, like fragment-cache sync)."""
        self.instance.sync_bus.broadcast("invalidate_privilege_cache", {})
        return ok()

    def _note_remote_write(self, schema: str, table: str):
        fcache = getattr(self.instance, "frag_cache", None)
        if fcache is not None:
            fcache.bump_epoch(f"{schema.lower()}.{table.lower()}")
        self.instance.sync_bus.broadcast(
            "invalidate_fragment_cache", {"schema": schema, "table": table})

    def _dml_match(self, tm: TableMeta, where: Optional[ast.ExprNode],
                   params: Optional[list], alias: str):
        """Evaluate WHERE on the host engine per partition -> (pid, row_ids)."""
        store = self.instance.store(tm.schema, tm.name)
        binder = Binder(self.instance.catalog, tm.schema, params or [])
        scope = Scope()
        fields = [(f"{alias}.{c.name}", c.dtype, tm.dictionaries.get(c.name.lower()))
                  for c in tm.columns]
        scope.add(alias, fields)
        pred = None
        if where is not None:
            cond = binder._bind_expr(where, scope)
            pred = ExprCompiler(np).compile_predicate(cond)
        ts = self._snapshot_ts()
        txn_id = self.txn.txn_id if self.txn is not None else 0
        for pid, p in enumerate(store.partitions):
            # snapshot visibility + lane references under the partition lock: a
            # concurrent append REBINDS the lanes (longer arrays), and mixing
            # pre-append visibility with post-append lanes tears the read
            # (caught by the concurrency stress suite).  The captured refs are
            # an immutable-length prefix, so the predicate can run unlocked;
            # the caller re-checks conflicts under the lock before stamping.
            with p.lock:
                vis = p.visible_mask(ts, txn_id)
                env = {}
                for c in tm.columns:
                    env[f"{alias}.{c.name}"] = (p.lanes[c.name],
                                                p.valid[c.name])
            if not vis.any():
                continue
            if pred is None:
                ids0 = np.nonzero(vis)[0]
                self._check_write_conflict(p, ids0)
                yield store, pid, ids0
                continue
            mask = pred(env) & vis
            ids = np.nonzero(mask)[0]
            if ids.size:
                self._check_write_conflict(p, ids)
                yield store, pid, ids

    def _check_write_conflict(self, p, ids: np.ndarray):
        """First-writer-wins SI: a row may be re-written only while its end stamp
        is INFINITY (or our own provisional stamp).  A provisional -txn stamp means
        a live txn holds it; a committed end_ts > our snapshot means a later
        committer already deleted it — overwriting either would lose that write
        (no lock waits -> no deadlocks; the reference's DeadlockDetectionTask
        becomes unnecessary by design)."""
        own = -self.txn.txn_id if self.txn is not None else None
        pend = p.end_ts[ids]
        conflict = pend != INFINITY_TS
        if own is not None:
            conflict &= (pend != own)
        if conflict.any():
            raise errors.TransactionError(
                "write conflict: row locked or deleted by a concurrent transaction")

    def _run_delete(self, stmt: ast.Delete, params: Optional[list]) -> ResultSet:
        schema = self._require_schema()
        tm = self.instance.catalog.table(stmt.table.schema or schema, stmt.table.table)
        rrs = self._remote_dml(tm)
        if rrs is not None:
            return rrs
        ts, txn = self._dml_ts()
        alias = (stmt.table.alias or stmt.table.table).lower()
        n = 0
        for store, pid, ids in self._dml_match(tm, stmt.where, params, alias):
            p = store.partitions[pid]
            with p.lock:
                # re-check under the lock: the check in _dml_match and this stamp
                # are otherwise not atomic against the archiver/other sessions
                self._check_write_conflict(p, ids)
                old_end = p.end_ts[ids].copy()
                self.instance.cdc.capture_rows(tm, store, pid, ids, "delete",
                                               ts, txn, self)
                self._gsi_delete(tm, store, pid, ids, ts, txn)
                p.delete_rows(ids, ts)
            if txn is not None:
                txn.deleted.append((store, pid, ids, old_end))
            n += ids.size
        tm.stats.row_count = max(tm.stats.row_count - n, 0)
        tm.bump_version()
        self._note_write(tm)
        self.instance.catalog.version += 1
        return ok(affected=n)

    def _run_update(self, stmt: ast.Update, params: Optional[list]) -> ResultSet:
        schema = self._require_schema()
        if not isinstance(stmt.table, ast.TableName):
            raise errors.NotSupportedError("multi-table UPDATE")
        tm = self.instance.catalog.table(stmt.table.schema or schema, stmt.table.table)
        rrs = self._remote_dml(tm)
        if rrs is not None:
            return rrs
        ts, txn = self._dml_ts()
        alias = (stmt.table.alias or stmt.table.table).lower()
        binder = Binder(self.instance.catalog, schema, params or [])
        scope = Scope()
        fields = [(f"{alias}.{c.name}", c.dtype, tm.dictionaries.get(c.name.lower()))
                  for c in tm.columns]
        scope.add(alias, fields)
        sets: List[Tuple[str, Any]] = []
        for name, vexpr in stmt.sets:
            cm = tm.column(name.simple)
            e = binder._bind_expr(vexpr, scope)
            target = cm.dtype
            if target.is_string and isinstance(e, ir.Literal) \
                    and isinstance(e.value, str):
                # SET strcol = 'literal': encode into the column's dictionary
                # (growing it if new) — the lane stores codes, not text
                d_ = tm.dictionaries[cm.name.lower()]
                code = np.asarray(d_.encode_one(e.value, add=True), np.int32)
                sets.append((cm.name, lambda env, _c=code: (_c, None)))
                continue
            if not (e.dtype.clazz == target.clazz and e.dtype.scale == target.scale) \
                    and e.dtype.clazz != dt.TypeClass.NULL and not target.is_string:
                e = ir.Cast(e, target)
            sets.append((cm.name, ExprCompiler(np).compile(e)))
        n = 0
        for store, pid, ids in self._dml_match(tm, stmt.where, params, alias):
            p = store.partitions[pid]
            # append_lock BEFORE the partition lock (the ordering every
            # appender follows): update_rows appends new MVCC versions, and
            # a concurrent inserter deriving its appended ranges must not
            # attribute them to itself (see TableStore.append_lock)
            with store.append_lock, p.lock:
                # re-check under the lock (see _run_delete) and read the lanes at
                # a consistent length with the stamp we are about to write
                self._check_write_conflict(p, ids)
                env = {}
                for c in tm.columns:
                    env[f"{alias}.{c.name}"] = (p.lanes[c.name][ids],
                                                p.valid[c.name][ids])
                new_lanes: Dict[str, np.ndarray] = {}
                new_valid: Dict[str, np.ndarray] = {}
                for cname, fn in sets:
                    cm = tm.column(cname)
                    d, v = fn(env)
                    d = np.broadcast_to(np.asarray(d),
                                        (ids.size,)).astype(cm.dtype.lane)
                    vm = np.ones(ids.size, np.bool_) if v is None else \
                        np.broadcast_to(np.asarray(v), (ids.size,))
                    new_lanes[cm.name] = d
                    new_valid[cm.name] = vm.copy()
                old_end = p.end_ts[ids].copy()
                self.instance.cdc.capture_rows(tm, store, pid, ids, "delete",
                                               ts, txn, self)
                self._gsi_delete(tm, store, pid, ids, ts, txn)
                start = p.num_rows
                p.update_rows(ids, new_lanes, new_valid, ts)
                if txn is not None:
                    txn.deleted.append((store, pid, ids, old_end))
                    txn.inserted.append((store, pid, start, ids.size))
                self._gsi_write_rows(tm, store, pid, start, ids.size, ts, txn)
                self.instance.cdc.capture_range(tm, store, pid, start, ids.size,
                                                ts, txn, self)
            n += ids.size
        tm.bump_version()
        self._note_write(tm)
        self.instance.catalog.version += 1
        return ok(affected=n)

    # -- DDL ----------------------------------------------------------------------

    def _run_create_view(self, stmt: ast.CreateView) -> ResultSet:
        from galaxysql_tpu.meta.catalog import ViewDef
        schema = stmt.name.schema or self._require_schema()
        # validate now: the view must bind against current metadata, and an
        # explicit column list must match the SELECT's output arity
        plan = self.instance.planner.bind_statement(stmt.select, schema, [], self)
        if stmt.columns is not None and \
                len(stmt.columns) != len(plan.display_names):
            raise errors.TddlError(
                f"View '{stmt.name.table}' column list length mismatch")
        v = ViewDef(schema, stmt.name.table, stmt.columns, stmt.select_sql)
        self.instance.catalog.add_view(v, or_replace=stmt.or_replace)
        self.instance.metadb.save_view(v)
        return ok()

    def _run_drop_view(self, stmt: ast.DropView) -> ResultSet:
        schema_default = self._require_schema()
        for nm in stmt.names:
            schema = nm.schema or schema_default
            if self.instance.catalog.drop_view(schema, nm.table, stmt.if_exists):
                self.instance.metadb.drop_view(schema, nm.table)
        return ok()

    def _run_create_table(self, stmt: ast.CreateTable) -> ResultSet:
        schema = stmt.name.schema or self._require_schema()
        if stmt.like is not None:
            src = self.instance.catalog.table(stmt.like.schema or schema,
                                              stmt.like.table)
            tm = TableMeta(schema, stmt.name.table, src.columns, src.primary_key,
                           src.partition, src.indexes)
        else:
            cols = []
            pk = list(stmt.primary_key)
            for cd in stmt.columns:
                typ = dt.from_sql_name(
                    cd.type_name + (" UNSIGNED" if cd.unsigned else ""),
                    cd.precision, cd.scale)
                default = None
                if cd.default is not None and not isinstance(cd.default, ast.NullLit):
                    default = _ast_literal_value(cd.default)
                cols.append(ColumnMeta(cd.name, typ, cd.nullable and not cd.primary_key,
                                       default, cd.auto_increment, cd.comment))
                if cd.primary_key:
                    pk.append(cd.name)
            part = _partition_info(stmt, cols)
            indexes = [IndexMeta(i.name or f"i_{k}", i.columns, i.unique,
                                 i.global_index, i.covering)
                       for k, i in enumerate(stmt.indexes) if i.columns]
            tm = TableMeta(schema, stmt.name.table, cols, pk, part, indexes,
                           stmt.comment)
        added = self.instance.catalog.add_table(tm, stmt.if_not_exists)
        if added:
            self.instance.register_table(tm)
            self.instance.metadb.save_schema(schema)
            self.instance.metadb.notify(f"table.{schema}.{tm.name}")
            from galaxysql_tpu.utils import events
            events.publish("ddl", f"CREATE TABLE {schema}.{tm.name}",
                           node=self.instance.node_id, schema=schema,
                           table=tm.name)
        return ok()

    def _run_drop_table(self, stmt: ast.DropTable) -> ResultSet:
        from galaxysql_tpu.utils import events
        schema = self._require_schema()
        for name in stmt.names:
            s = name.schema or schema
            recycle = self.instance.config.get("ENABLE_RECYCLEBIN", self.vars)
            if recycle:
                try:
                    tm = self.instance.catalog.table(s, name.table)
                except errors.TddlError:
                    tm = None
                if tm is not None and self.instance.recycle.drop(tm):
                    # parked in the bin (FLASHBACK can restore)
                    events.publish("ddl",
                                   f"DROP TABLE {s}.{name.table} (recycled)",
                                   node=self.instance.node_id, schema=s,
                                   table=name.table)
                    continue
            if self.instance.catalog.drop_table(s, name.table, stmt.if_exists):
                self.instance.drop_store(s, name.table)
                events.publish("ddl", f"DROP TABLE {s}.{name.table}",
                               node=self.instance.node_id, schema=s,
                               table=name.table)
        return ok()

    def _run_check_table(self, stmt: ast.CheckTable) -> ResultSet:
        from galaxysql_tpu.server.maintain import check_table
        schema = self._require_schema()
        rows = []
        # a span a table (`analyze:<table>`, rows= and its seconds) where the
        # session asked for its statements' trees or a profiler session records
        annotate = tracing.device_trace_active()
        tc = None
        if annotate or self.vars.get("ENABLE_QUERY_TRACING"):
            tc = tracing.TraceContext(self.instance.trace_ids.next(),
                                      node=self.instance.node_id,
                                      annotate=annotate)
        for name in stmt.names:
            tm = self.instance.catalog.table(name.schema or schema, name.table)
            if getattr(tm, "remote", None) is not None:
                raise errors.NotSupportedError(
                    f"CHECK TABLE on worker-resident table '{tm.name}' is not "
                    "supported from this CN (run it on the worker)")
            store = self.instance.store(tm.schema, tm.name)
            rows.extend(check_table(self.instance, tm, store))
        return ResultSet(["Table", "Op", "Msg_type", "Msg_text"],
                         [dt.VARCHAR] * 4, rows)

    def _run_flashback_table(self, stmt: ast.FlashbackTable) -> ResultSet:
        schema = stmt.name.schema or self._require_schema()
        restored = self.instance.recycle.flashback(schema, stmt.name.table,
                                                   stmt.rename_to)
        return ok(info=f"restored as {restored}")

    def _run_purge(self, stmt: ast.PurgeRecycleBin) -> ResultSet:
        n = self.instance.recycle.purge(stmt.name)
        return ok(affected=n)

    def _run_advise_index(self, stmt: ast.AdviseIndex,
                          params: Optional[list]) -> ResultSet:
        from galaxysql_tpu.server.maintain import advise_indexes
        schema = self._require_schema()
        plan = self.instance.planner.bind_statement(stmt.select, schema,
                                                    params or [], self)
        rows = advise_indexes(self.instance, plan)
        return ResultSet(["TABLE", "COLUMN", "REASON", "SUGGESTION"],
                         [dt.VARCHAR] * 4, rows)

    def _run_truncate(self, stmt: ast.TruncateTable) -> ResultSet:
        schema = self._require_schema()
        tm = self.instance.catalog.table(stmt.name.schema or schema, stmt.name.table)
        self.instance.store(tm.schema, tm.name).truncate()
        tm.bump_version()
        self._note_write(tm)
        self.instance.catalog.version += 1
        return ok()

    def _drop_database(self, stmt: ast.DropDatabase):
        cat = self.instance.catalog
        key = stmt.name.lower()
        if key in cat.schemas:
            for t in list(cat.schemas[key].tables.values()):
                self.instance.drop_store(t.schema, t.name)
        cat.drop_schema(stmt.name, stmt.if_exists)
        self.instance.metadb.drop_schema(stmt.name)
        if self.schema and self.schema.lower() == key:
            self.schema = None

    def _run_analyze(self, stmt: ast.AnalyzeTable) -> ResultSet:
        schema = self._require_schema()
        rows = []
        # a span a table (`analyze:<table>`, rows= and its seconds) where the
        # session asked for its statements' trees or a profiler session records
        annotate = tracing.device_trace_active()
        tc = None
        if annotate or self.vars.get("ENABLE_QUERY_TRACING"):
            tc = tracing.TraceContext(self.instance.trace_ids.next(),
                                      node=self.instance.node_id,
                                      annotate=annotate)
        for name in stmt.names:
            tm = self.instance.catalog.table(name.schema or schema, name.table)
            store = self.instance.store(tm.schema, tm.name)
            from galaxysql_tpu.meta.statistics import analyze_store
            # per-partition HLL sketches merged + equi-depth histograms
            # (Histogram.java / statistic/ndv analog)
            with tc.span(f"analyze:{tm.name}", "analyze") if tc is not None \
                    else _NULL_CTX as sp:
                analyze_store(tm, store)
                if sp is not None:
                    sp.attrs["rows"] = tm.stats.row_count
            rows.append((f"{tm.schema}.{tm.name}", "analyze", "status", "OK"))
        if tc is not None:
            self.last_spans = list(tc.spans)
        self.instance.catalog.version += 1
        # fresh statistics re-arm HEAL_FAILED-parked plan baselines
        self.instance.catalog.stats_version += 1
        return ResultSet(["Table", "Op", "Msg_type", "Msg_text"],
                         [dt.VARCHAR] * 4, rows)

    # -- SET / SHOW / EXPLAIN ------------------------------------------------------

    def _run_set(self, stmt: ast.SetStmt) -> ResultSet:
        for scope, name, vexpr in stmt.assignments:
            value = _ast_literal_value(vexpr)
            if scope == "user":
                self.user_vars[name.lower()] = value
            elif scope == "global":
                self.instance.config.set_instance(name, value)
                # durable + fleet-visible: peers sharing the GMS reload via
                # the config listener (§5.6 config push analog)
                import json as _json
                self.instance.metadb.kv_put(
                    f"config.param.{name.upper()}", _json.dumps(value))
                self.instance.metadb.notify("config.params")
            else:
                self.vars[name.upper() if name.upper() in
                          self.instance.config.registry() else name.lower()] = value
        return ok()

    def _run_baseline(self, stmt: ast.BaselineStmt) -> ResultSet:
        """SPM DAL: BASELINE EVOLVE executes unaccepted candidates with their
        join order forced and promotes measurably faster ones; BASELINE DELETE
        drops a baseline (PlanManager DAL analog)."""
        spm = self.instance.planner.spm
        if stmt.action == "delete":
            found = spm.delete(stmt.baseline_id)
            return ok(affected=1 if found else 0)

        def measure(key, orders):
            schema, psql = key
            from galaxysql_tpu.sql.parser import parse as _parse
            pstmt = _parse(psql)
            params = spm.last_params(key)
            plan = self.instance.planner.bind_statement(
                pstmt, schema, params, self, forced_orders=orders)
            ctx = ExecContext(self.instance.stores, self._snapshot_ts(), params,
                              archive=self.instance.archive,
                              archive_instance=self.instance)
            op = build_operator(plan.rel, ctx)
            t0 = time.time()
            run_to_batch(op)
            return (time.time() - t0) * 1000.0

        rows = spm.evolve(measure)
        return ResultSet(["BASELINE_ID", "PROMOTED", "CANDIDATE_MS", "ACCEPTED_MS"],
                         [dt.BIGINT, dt.BOOL, dt.DOUBLE, dt.DOUBLE],
                         [(i, p, c, a) for i, p, c, a in rows])

    def _run_show(self, stmt: ast.Show) -> ResultSet:
        from galaxysql_tpu.server import show_handlers
        return show_handlers.handle(self, stmt)

    def _run_explain(self, stmt: ast.Explain, params) -> ResultSet:
        schema = self._require_schema()
        inner = stmt.stmt
        if not isinstance(inner, (ast.Select, ast.SetOpSelect)):
            return ResultSet(["plan"], [dt.VARCHAR], [("not a plannable statement",)])
        plan = self.instance.planner.bind_statement(inner, schema, params or [])
        lines = plan.explain().split("\n")
        col_views = None
        if stmt.analyze:
            from galaxysql_tpu.utils.tracing import (QueryProfile,
                                                     SEGMENT_TRACER)
            cache = None
            if plan.workload == "AP" and self.instance.config.get(
                    "ENABLE_TPU_ENGINE", self.vars):
                from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE
                cache = GLOBAL_DEVICE_CACHE
            # same engine configuration as the real execution path — analyze
            # numbers must describe the plan users actually run (device cache
            # and pipeline fusion included), not a cold host-only variant
            ctx = ExecContext(self.instance.stores, self._snapshot_ts(),
                              params or [], device_cache=cache,
                              archive=self.instance.archive,
                              archive_instance=self.instance,
                              hints=getattr(plan, "hints", None))
            ctx.collect_stats = True  # per-operator rows/time (RuntimeStatistics)
            # session-scoped SET ENABLE_SKEW_EXECUTION, same as the real path
            from galaxysql_tpu.exec import skew as _skew
            ctx.skew_modes = _skew.exec_modes(ctx.hints, self.instance,
                                              self.vars)
            # same columnar-replica routing as the real path: ANALYZE numbers
            # must describe the tier the query actually reads
            self._maybe_route_columnar(plan, ctx, None, schema)
            col_views = ctx.columnar
            prof = QueryProfile(trace_id=self.instance.trace_ids.next(),
                                sql="<explain analyze>", schema=schema,
                                conn_id=self.conn_id, started_at=time.time())
            ctx.profile = prof
            # compile/transfer attribution: deltas over the process counters
            # bracket this execution (host-side reads, free)
            from galaxysql_tpu.exec.device_cache import TRANSFER_STATS
            from galaxysql_tpu.exec.operators import COMPILE_STATS
            c0 = dict(COMPILE_STATS)
            x0 = dict(TRANSFER_STATS)
            from galaxysql_tpu.plan import logical as L
            mdl_keys = {f"{n.table.schema.lower()}.{n.table.name.lower()}"
                        for n in L.walk(plan.rel) if isinstance(n, L.Scan)}
            t0 = time.time()
            # statement-scope shared MDL: concurrent column DDL must not swap
            # partition lanes mid-execution (same torn-read class as SELECT)
            with self.instance.mdl.shared(mdl_keys), \
                    SEGMENT_TRACER.scoped(prof.segments):
                # same engine dispatch as _run_query_locked: ANALYZE numbers
                # must describe the engine users actually run — an AP query
                # above the MPP threshold reports its SPMD stages (per-shard
                # rows, skew, HotKeys/Salted decisions), not a local stand-in
                batch = self._try_mpp(plan, ctx, count=False)
                if batch is None:
                    op = build_operator(plan.rel, ctx)
                    batch = run_to_batch(op)
            elapsed = time.time() - t0
            rows = batch.num_live()
            # the operator tree annotated in place with measured rows/time —
            # operators inside fused segments included (per-stage counts from
            # the stats program variant, tagged `fused(<chain>)`)
            from galaxysql_tpu.plan.physical import annotate_explain
            lines = annotate_explain(plan.rel, ctx.op_stats,
                                     rf=getattr(ctx, "rf", None),
                                     skew_stats=getattr(ctx, "skew_stats",
                                                        None))
            d_retr = COMPILE_STATS["retraces"] - c0["retraces"]
            d_cms = COMPILE_STATS["compile_ms"] - c0["compile_ms"]
            d_cached = COMPILE_STATS["cache_hits"] - c0.get("cache_hits", 0)
            d_bytes = TRANSFER_STATS["bytes"] - x0["bytes"]
            d_xfers = TRANSFER_STATS["transfers"] - x0["transfers"]
            lines += [f"-- trace_id: {prof.trace_id}", f"-- rows: {rows}",
                      f"-- elapsed: {elapsed:.3f}s",
                      f"-- compile: retraces={d_retr} wall={d_cms:.3f}ms "
                      f"cached={d_cached}",
                      f"-- transfer: h2d_bytes={d_bytes} "
                      f"transfers={d_xfers}"] + \
                [f"-- {t}" for t in ctx.trace]
            for st in ctx.op_stats:
                tag = f" fused({st['segment']})" if st.get("fused") else ""
                lines.append(f"-- op {st['operator']}: rows={st['rows_out']} "
                             f"batches={st['batches']} "
                             f"wall={st['wall_ms']}ms{tag}")
            for sp in prof.segments:
                lines.append(f"-- segment {sp.segment_id} {sp.chain}: "
                             f"rows_in={sp.rows_in} rows_out={sp.rows_out} "
                             f"compiled={sp.compiled} wall={sp.wall_ms}ms")
            self._finish_query(prof.sql, elapsed, prof, plan.workload,
                               "local", rows, ctx, plan=plan)
        if col_views is None:
            # plain EXPLAIN: dry-run the routing decision against a throwaway
            # probe so freshness shows up without executing anything
            class _Probe:
                pass
            probe = _Probe()
            probe.hints = getattr(plan, "hints", None) or {}
            probe.txn_id = 0
            probe.snapshot_ts = None
            probe.columnar = {}
            self._maybe_route_columnar(plan, probe, None, schema)
            col_views = probe.columnar
        if col_views:
            from galaxysql_tpu.meta.tso import LOGICAL_BITS as _LB
            for key in sorted(col_views):
                v = col_views[key]
                lag = max(time.time() * 1000.0 - (v.watermark >> _LB), 0.0)
                lines.append(f"-- columnar: {key} watermark={v.watermark} "
                             f"freshness_lag_ms={lag:.1f} "
                             f"stripes={len(v.stripes)} "
                             f"delta_chunks={len(v.delta)}")
        lines.append(f"-- workload: {plan.workload}")
        return ResultSet(["plan"], [dt.VARCHAR], [(l,) for l in lines])

    def _describe(self, name: ast.TableName) -> ResultSet:
        schema = self._require_schema()
        tm = self.instance.catalog.table(name.schema or schema, name.table)
        rows = []
        for c in tm.columns:
            key = "PRI" if c.name in tm.primary_key else ""
            rows.append((c.name, c.dtype.sql_name().lower(),
                         "YES" if c.nullable else "NO", key,
                         None if c.default is None else str(c.default),
                         "auto_increment" if c.auto_increment else ""))
        return ResultSet(["Field", "Type", "Null", "Key", "Default", "Extra"],
                         [dt.VARCHAR] * 6, rows)


def _partition_info(stmt: ast.CreateTable, cols: List[ColumnMeta]) -> PartitionInfo:
    if stmt.broadcast:
        return PartitionInfo("broadcast")
    if stmt.single or stmt.partition is None:
        return SINGLE
    p = stmt.partition
    colnames = []
    for e in p.exprs:
        if isinstance(e, ast.Name):
            colnames.append(e.simple)
        else:
            raise errors.NotSupportedError("partition expressions must be columns")
    boundaries = []
    by_name = {c.name.lower(): c for c in cols}
    for pname, vals in p.boundaries:
        enc = []
        for v in vals:
            if isinstance(v, ast.Name) and v.simple.upper() == "MAXVALUE":
                enc.append(None)
            else:
                lit = _ast_literal_value(v)
                cm = by_name.get(colnames[0].lower())
                from galaxysql_tpu.meta.catalog import encode_partition_value
                enc.append(encode_partition_value(lit, cm.dtype) if cm else lit)
        boundaries.append((pname, enc))
    count = p.count or (len(boundaries) if boundaries else 8)
    return PartitionInfo(p.method, colnames, count, boundaries)


def _ast_literal_value(e: ast.ExprNode):
    if isinstance(e, ast.NumberLit):
        return e.value
    if isinstance(e, ast.StringLit):
        return e.value
    if isinstance(e, ast.NullLit):
        return None
    if isinstance(e, ast.BoolLit):
        return 1 if e.value else 0
    if isinstance(e, ast.Unary) and e.op == "-":
        return -_ast_literal_value(e.arg)
    if isinstance(e, ast.Func):
        return str(e.name)
    if isinstance(e, ast.DateLit):
        return e.value
    raise errors.NotSupportedError("expected literal value")


def _fold_constant(e: ir.Expr) -> ir.Literal:
    f = ExprCompiler(np).compile(e)
    d, v = f({})
    if v is not None and not np.all(np.asarray(v)):
        return ir.Literal(None, e.dtype)
    val = np.asarray(d).item()  # galaxylint: disable=jit-device-sync -- np-backend constant fold at bind time: d is a host numpy scalar, no device involved
    if e.dtype.clazz == dt.TypeClass.DECIMAL:
        val = val / (10 ** e.dtype.scale)
    return ir.Literal(val, e.dtype)
