"""Per-query runtime statistics: QueryProfile threading, EXPLAIN ANALYZE
actual-rows annotations (fused segments included), SHOW FULL STATS /
information_schema surfaces, the metrics registry, web endpoints, and the
no-profiling hot-path dispatch guard.

The `observability`-marked tests are the fast smoke target (`make obs-smoke`).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.fusion import FusedPipelineOp, FusedSegment
from galaxysql_tpu.exec.operators import SourceOp
from galaxysql_tpu.expr import ir
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils.metrics import MetricsRegistry
from galaxysql_tpu.utils.tracing import SEGMENT_TRACER


@pytest.fixture(scope="module")
def tpch_session():
    from galaxysql_tpu.storage import tpch
    data = tpch.generate(0.01)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_pylists(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    yield s
    s.close()


def _analyze_lines(s, sql):
    return [r[0] for r in s.execute("EXPLAIN ANALYZE " + sql).rows]


def _top_actual_rows(lines):
    """actual rows= annotation of the tree's root line."""
    import re
    m = re.search(r"actual rows=(\d+)", lines[0])
    assert m, f"root line not annotated: {lines[0]!r}"
    return int(m.group(1))


# -- metrics registry ---------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_rows_and_prometheus(self):
        reg = MetricsRegistry(namespace="test")
        reg.counter("hits", "cache hits").inc(3)
        reg.gauge("depth", "queue depth").set(2.5)
        rows = {n: (k, v) for n, k, v, _h in reg.rows()}
        assert rows["hits"] == ("counter", 3)
        assert rows["depth"] == ("gauge", 2.5)
        text = reg.prometheus_text()
        assert "# TYPE test_hits counter" in text
        assert "test_hits 3" in text
        assert "test_depth 2.5" in text

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_counter_map_adapter(self):
        reg = MetricsRegistry()
        cm = reg.counter_map("engine")
        cm["mpp_queries"] += 1
        cm["mpp_queries"] += 2
        assert cm["mpp_queries"] == 3
        assert cm.get("missing", 7) == 7
        assert dict(cm) == {"mpp_queries": 3}
        assert ("engine_mpp_queries", "counter", 3) in \
            [(n, k, v) for n, k, v, _ in reg.rows()]


# -- per-query profiles -------------------------------------------------------


@pytest.mark.observability
class TestQueryProfiles:
    @pytest.fixture(scope="class")
    def session(self):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE obs")
        s.execute("USE obs")
        s.execute("CREATE TABLE t (a BIGINT PRIMARY KEY, b BIGINT)")
        inst.store("obs", "t").insert_pylists(
            {"a": list(range(4000)), "b": [i % 11 for i in range(4000)]},
            inst.tso.next_timestamp())
        yield s
        s.close()

    def test_default_path_records_lightweight_profile(self, session):
        inst = session.instance
        r = session.execute("SELECT count(*) FROM t WHERE a < 100")
        p = inst.profiles.entries()[-1]
        assert p.sql.startswith("SELECT count(*)")
        assert not p.profiled and p.op_stats == [] and p.segments == []
        assert p.rows == len(r.rows) == 1
        assert p.elapsed_ms > 0 and p.trace_id > 0
        # trace ids are monotonic across queries
        session.execute("SELECT count(*) FROM t")
        assert inst.profiles.entries()[-1].trace_id > p.trace_id
        # and the session trace links to the profile
        assert f"trace-id {inst.profiles.entries()[-1].trace_id}" in \
            session.last_trace

    def test_profiling_collects_operators_and_segments(self, session):
        inst = session.instance
        session.execute("SET ENABLE_QUERY_PROFILING = 1")
        try:
            r = session.execute("SELECT a, b * 2 FROM t WHERE a < 500")
        finally:
            session.execute("SET ENABLE_QUERY_PROFILING = 0")
        p = inst.profiles.entries()[-1]
        assert p.profiled
        by_op = {st["operator"]: st for st in p.op_stats}
        assert by_op["Scan"]["rows_out"] == 4000
        assert by_op["Filter"]["rows_out"] == 500 and by_op["Filter"]["fused"]
        assert by_op["Project"]["rows_out"] == 500
        assert [sp.chain for sp in p.segments] == ["filter>project"]
        assert p.segments[0].rows_in == 4000 and p.segments[0].rows_out == 500
        assert p.rows == len(r.rows) == 500

    def test_point_path_profiles_and_slow_links(self, session):
        from galaxysql_tpu.utils.tracing import SLOW_LOG
        inst = session.instance
        SLOW_LOG.clear()
        session.execute("SET SLOW_SQL_MS = 0")
        try:
            session.execute("SELECT b FROM t WHERE a = 7")
            session.execute("SELECT b FROM t WHERE a = 7")  # point-plan hit
        finally:
            session.execute("SET SLOW_SQL_MS = -1")
        p = inst.profiles.entries()[-1]
        assert p.engine == "point" and p.workload == "TP"
        # SHOW SLOW rows carry the trace id + workload linking to the profile
        rows = session.execute("SHOW SLOW").rows
        assert any(row[3] == p.trace_id and row[4] == "TP" for row in rows)


# -- MPP per-stage / per-shard stats ------------------------------------------


@pytest.mark.observability
class TestMppStageStats:
    def test_profile_carries_stage_and_shard_rows(self):
        inst = Instance()
        if inst.mesh() is None:
            pytest.skip("single device: no MPP mesh")
        s = Session(inst)
        s.execute("CREATE DATABASE mob; USE mob")
        s.execute("CREATE TABLE big (k VARCHAR(4), v BIGINT)")
        rng = np.random.default_rng(0)
        inst.store("mob", "big").insert_arrays(
            {"k": np.array(["x", "y", "z"])[rng.integers(0, 3, 60_000)],
             "v": rng.integers(0, 1000, 60_000)}, inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE big")
        s.vars["MPP_MIN_AP_ROWS"] = 1000
        s.vars["ENABLE_QUERY_PROFILING"] = True
        r = s.execute("SELECT k, sum(v) FROM big GROUP BY k ORDER BY k")
        assert len(r.rows) == 3
        p = inst.profiles.entries()[-1]
        assert p.engine == "mpp" and p.profiled
        mpp_stats = [st for st in p.op_stats if st.get("engine") == "mpp"]
        assert any(st["operator"] == "Scan" for st in mpp_stats)
        scan = next(st for st in mpp_stats if st["operator"] == "Scan")
        # per-shard task stats: shard-local row counts sum to the scan total
        assert "rows_per_shard" in scan
        assert sum(scan["rows_per_shard"]) == scan["rows_out"] == 60_000
        agg = next(st for st in mpp_stats if st["operator"] == "Aggregate")
        assert agg["rows_out"] == 3 and agg["replicated"]
        s.close()


# -- EXPLAIN ANALYZE ----------------------------------------------------------


@pytest.mark.observability
class TestExplainAnalyze:
    def test_q1_actual_rows_match_resultset(self, tpch_session):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        s = tpch_session
        rs = s.execute(QUERIES[1])
        lines = _analyze_lines(s, QUERIES[1])
        assert _top_actual_rows(lines) == len(rs.rows)
        # operators INSIDE the fused filter>project chain are annotated
        fused = [l for l in lines if "fused(" in l]
        assert any("Filter" in l and "actual rows=" in l for l in fused)
        assert any("Project" in l and "actual rows=" in l for l in fused)
        assert any(l.startswith("-- segment ") for l in lines)
        assert any("wall=" in l for l in lines)

    def test_q3_actual_rows_match_resultset(self, tpch_session):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        s = tpch_session
        rs = s.execute(QUERIES[3])
        lines = _analyze_lines(s, QUERIES[3])
        assert _top_actual_rows(lines) == len(rs.rows)
        assert any("Join" in l and "actual rows=" in l for l in lines)

    def test_profile_recorded_for_analyze(self, tpch_session):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        s = tpch_session
        _analyze_lines(s, QUERIES[1])
        p = s.instance.profiles.entries()[-1]
        assert p.profiled and p.op_stats


# -- SQL surfaces -------------------------------------------------------------


@pytest.mark.observability
class TestSqlSurfaces:
    @pytest.fixture(scope="class")
    def session(self):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE surf")
        s.execute("USE surf")
        s.execute("CREATE TABLE t (a BIGINT)")
        inst.store("surf", "t").insert_pylists(
            {"a": list(range(100))}, inst.tso.next_timestamp())
        yield s
        s.close()

    def test_show_full_stats_lists_profiles(self, session):
        session.execute("SELECT count(*) FROM t")
        r = session.execute("SHOW FULL STATS")
        assert r.names[0] == "Trace_id"
        assert r.rows, "profiles should be retained"
        newest = r.rows[0]
        assert newest[0] == session.instance.profiles.entries()[-1].trace_id
        sql_col = r.names.index("SQL")
        assert newest[sql_col].lower().startswith("show full stats") or \
            "count" in newest[sql_col]
        assert "Max_shard_rows" in r.names  # per-shard skew triage column
        # SHOW STATS (without FULL) stays the instance-counter surface
        plain = session.execute("SHOW STATS")
        assert plain.names == ["Name", "Value"]

    def test_metrics_roundtrip_counter_bump(self, session):
        inst = session.instance
        before_rows = session.execute(
            "SELECT value FROM information_schema.metrics "
            "WHERE metric_name = 'engine_obs_test_bumps'").rows
        before = before_rows[0][0] if before_rows else 0
        inst.counters["obs_test_bumps"] += 3
        r = session.execute(
            "SELECT metric_kind, value FROM information_schema.metrics "
            "WHERE metric_name = 'engine_obs_test_bumps'")
        assert r.rows == [("counter", float(before) + 3.0)]
        # SHOW METRICS renders the same registry
        rows = {row[0]: row[2] for row in session.execute("SHOW METRICS").rows}
        assert rows["engine_obs_test_bumps"] == float(before) + 3.0

    def test_query_stats_virtual_table(self, session):
        session.execute("SELECT count(*) FROM t WHERE a > 5")
        r = session.execute(
            "SELECT trace_id, engine, rows_returned FROM "
            "information_schema.query_stats")
        assert len(r.rows) >= 2
        ids = [row[0] for row in r.rows]
        assert ids == sorted(ids)  # ring order: oldest -> newest


# -- query-scoped segment tracer ----------------------------------------------


@pytest.mark.observability
class TestScopedSegmentTracer:
    def test_two_sessions_do_not_interleave(self):
        """Two sessions profiling concurrently: each QueryProfile holds only
        its own segment spans."""
        inst = Instance()
        s0 = Session(inst)
        s0.execute("CREATE DATABASE il")
        s0.execute("USE il")
        s0.execute("CREATE TABLE big (a BIGINT, b BIGINT)")
        s0.execute("CREATE TABLE small (a BIGINT, b BIGINT)")
        inst.store("il", "big").insert_pylists(
            {"a": list(range(3000)), "b": list(range(3000))},
            inst.tso.next_timestamp())
        inst.store("il", "small").insert_pylists(
            {"a": list(range(700)), "b": list(range(700))},
            inst.tso.next_timestamp())

        results = {}
        barrier = threading.Barrier(2)

        def run(name, table, rounds=8):
            s = Session(inst, "il")
            s.vars["ENABLE_QUERY_PROFILING"] = True
            barrier.wait()
            profs = []
            for _ in range(rounds):
                s.execute(f"SELECT a, b + 1 FROM {table} WHERE a >= 0")
                tid = int(s.last_trace[0].split()[-1])  # "trace-id N"
                profs.append(inst.profiles.get(tid))
            results[name] = profs
            s.close()

        t1 = threading.Thread(target=run, args=("big", "big"))
        t2 = threading.Thread(target=run, args=("small", "small"))
        t1.start(); t2.start()
        t1.join(); t2.join()

        for name, expect in (("big", 3000), ("small", 700)):
            for p in results[name]:
                assert p is not None and p.segments, name
                # every span in this query's profile is from ITS table
                assert all(sp.rows_out == expect for sp in p.segments), (
                    name, [(sp.chain, sp.rows_out) for sp in p.segments])

    def test_scopes_nest_and_unscoped_dispatch_records_nothing(self):
        b = ColumnBatch({"a": Column(jnp.arange(2048), None,
                                     dt.BIGINT, None)}, None)
        seg = FusedSegment([("filter",
                             ir.call("lt", ir.ColRef("a", dt.BIGINT, None),
                                     ir.lit(100)))])
        with SEGMENT_TRACER.scoped() as outer:
            seg.run_batch(b)
            with SEGMENT_TRACER.scoped() as inner:
                seg.run_batch(b)
                seg.run_batch(b)
            seg.run_batch(b)  # the previous sink is restored on exit
        assert (len(outer), len(inner)) == (2, 2)
        assert not SEGMENT_TRACER.active
        seg.run_batch(b)  # no scope: no span, no device sync
        assert (len(outer), len(inner)) == (2, 2)


# -- web console --------------------------------------------------------------


@pytest.mark.observability
class TestWebObservability:
    @pytest.fixture(scope="class")
    def console(self):
        from galaxysql_tpu.server.web import WebConsole
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE wob")
        s.execute("USE wob")
        s.execute("CREATE TABLE t (a BIGINT)")
        inst.store("wob", "t").insert_pylists(
            {"a": list(range(50))}, inst.tso.next_timestamp())
        s.execute("SELECT count(*) FROM t")
        web = WebConsole(inst)
        port = web.start()
        yield inst, s, port
        web.stop()
        s.close()

    def test_metrics_prometheus_format(self, console):
        _inst, _s, port = console
        req = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10)
        assert req.headers["Content-Type"].startswith("text/plain")
        text = req.read().decode()
        assert "# TYPE galaxysql_queries_total counter" in text
        assert "galaxysql_queries_total" in text
        assert "galaxysql_sessions_active" in text

    def test_query_profile_endpoint(self, console):
        inst, s, port = console
        s.vars["ENABLE_QUERY_PROFILING"] = True
        try:
            s.execute("SELECT a FROM t WHERE a < 10")
        finally:
            s.vars.pop("ENABLE_QUERY_PROFILING", None)
        tid = inst.profiles.entries()[-1].trace_id
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/query/{tid}", timeout=10) as r:
            d = json.loads(r.read())
        assert d["trace_id"] == tid and d["profiled"]
        assert d["op_stats"] and all("node_id" not in st
                                     for st in d["op_stats"])
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/query/999999999", timeout=10)

    def test_query_stats_listing(self, console):
        inst, _s, port = console
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/query-stats", timeout=10) as r:
            d = json.loads(r.read())
        assert d["queries"]
        assert d["queries"][0]["trace_id"] == \
            inst.profiles.entries()[-1].trace_id


# -- hot-path guard: profiling off costs zero extra dispatches ----------------


@pytest.mark.observability
class TestNoProfilingHotPath:
    def test_fused_chain_one_dispatch_per_batch(self):
        """The PR-1 dispatch invariant survives the observability layer: a
        fused filter→project chain still pays exactly ONE streaming dispatch
        per batch when profiling is off (the stats program variant is a
        different cache key, never the default)."""
        rng = np.random.default_rng(3)
        B, n = 8, 1 << 17  # device path (capacity > TP_HOST_ROWS)
        batches = []
        for _ in range(B):
            a = jnp.asarray(rng.integers(0, 1 << 20, n))
            batches.append(ColumnBatch(
                {"a": Column(a, None, dt.BIGINT, None)}, None))
        ca = ir.ColRef("a", dt.BIGINT, None)
        seg = FusedSegment([("filter", ir.call("lt", ca, ir.lit(1 << 19))),
                            ("project", [("c", ir.call("mul", ca,
                                                       ir.lit(2)))])])

        def drain():
            for out in FusedPipelineOp(SourceOp(batches), seg).batches():
                out.live_mask()
        drain()  # warmup: compile
        ops.reset_dispatch_stats()
        drain()
        assert ops.DISPATCH_STATS["dispatches"] == B

    def test_steady_state_dispatches_unchanged_after_profiled_run(self):
        """Profiling a query must not perturb the subsequent non-profiled
        executions (same program cache entries, same dispatch count)."""
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE hp")
        s.execute("USE hp")
        s.execute("CREATE TABLE t (a BIGINT, b BIGINT)")
        inst.store("hp", "t").insert_pylists(
            {"a": list(range(3000)), "b": list(range(3000))},
            inst.tso.next_timestamp())
        q = "SELECT a, b * 3 FROM t WHERE a < 1500"
        s.execute(q)  # warmup
        ops.reset_dispatch_stats()
        s.execute(q)
        baseline = ops.DISPATCH_STATS["dispatches"]
        s.vars["ENABLE_QUERY_PROFILING"] = True
        s.execute(q)  # profiled run (may dispatch differently — allowed)
        s.vars.pop("ENABLE_QUERY_PROFILING", None)
        ops.reset_dispatch_stats()
        s.execute(q)
        assert ops.DISPATCH_STATS["dispatches"] == baseline
        s.close()
