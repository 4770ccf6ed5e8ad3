"""MPP engine tests on the 8-virtual-device CPU mesh: distributed results must equal
the single-device engine's (the LocalServer-style in-proc cluster test, SURVEY.md §4).

Coverage: ALL 22 TPC-H queries, all 13 SSB queries, window/union/distinct shapes,
archive-table scans, the shuffle path, and the session-level dispatch (MPP actually
runs, and fallback is counted + traced, never silent)."""

import numpy as np
import pytest

from galaxysql_tpu.parallel.mesh import make_mesh
from galaxysql_tpu.parallel.mpp import MppExecutor
from galaxysql_tpu.plan.physical import ExecContext
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import ssb, tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES
from galaxysql_tpu.utils import errors


@pytest.fixture(scope="module")
def env():
    import jax
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    data = tpch.generate(0.01)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    mesh = make_mesh(8)
    yield inst, s, mesh
    s.close()


@pytest.fixture(scope="module")
def ssb_env():
    data = ssb.generate(0.005)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE ssb; USE ssb")
    for t in ssb.TABLE_ORDER:
        s.execute(ssb.SSB_DDL[t])
        inst.store("ssb", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(ssb.TABLE_ORDER))
    mesh = make_mesh(8)
    yield inst, s, mesh
    s.close()


def run_mpp(inst, s, mesh, sql, schema="tpch"):
    plan = inst.planner.plan_select(sql, schema)
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    ex = MppExecutor(ctx, mesh)
    return ex.execute(plan.rel)


def rows_of(batch):
    return batch.to_pylist()


def assert_same(mpp_rows, local_rows, ordered):
    if not ordered:
        keyf = lambda r: tuple(str(x) for x in r)
        mpp_rows = sorted(mpp_rows, key=keyf)
        local_rows = sorted(local_rows, key=keyf)
    assert len(mpp_rows) == len(local_rows)
    for a, b in zip(mpp_rows, local_rows):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            if isinstance(x, float) and isinstance(y, float):
                assert abs(x - y) <= max(abs(y) * 1e-6, 1e-6)
            else:
                assert x == y


# every TPC-H query distributes; True = result is ordered (compare in order)
TPCH_ORDERED = {1: True, 2: True, 3: True, 4: True, 5: True, 6: False, 7: True,
                8: True, 9: True, 10: True, 11: True, 12: True, 13: True,
                14: False, 15: True, 16: True, 17: False, 18: True, 19: False,
                20: True, 21: True, 22: True}


@pytest.mark.parametrize("qid", sorted(TPCH_ORDERED))
def test_tpch_mpp_matches_local(env, qid):
    inst, s, mesh = env
    sql = QUERIES[qid]
    local = s.execute(sql)
    mpp = run_mpp(inst, s, mesh, sql)
    assert_same(rows_of(mpp), local.rows, TPCH_ORDERED[qid])


@pytest.mark.parametrize("qid", sorted(ssb.QUERIES))
def test_ssb_mpp_matches_local(ssb_env, qid):
    inst, s, mesh = ssb_env
    sql = ssb.QUERIES[qid]
    local = s.execute(sql)
    mpp = run_mpp(inst, s, mesh, sql, "ssb")
    assert_same(rows_of(mpp), local.rows, True)


def test_shuffle_join_path(env):
    """Force the hash-shuffle path by dropping the broadcast threshold."""
    import galaxysql_tpu.parallel.mpp as M
    inst, s, mesh = env
    old = M.BROADCAST_BUILD_LIMIT
    M.BROADCAST_BUILD_LIMIT = 0
    try:
        sql = ("SELECT o_orderpriority, count(*) AS n FROM orders, lineitem "
               "WHERE o_orderkey = l_orderkey AND l_quantity < 10 "
               "GROUP BY o_orderpriority ORDER BY o_orderpriority")
        local = s.execute(sql)
        mpp = run_mpp(inst, s, mesh, sql)
        assert_same(rows_of(mpp), local.rows, True)
    finally:
        M.BROADCAST_BUILD_LIMIT = old


SPARSE_JOINS = {
    "inner": "SELECT o_orderkey, l_linenumber FROM orders, lineitem "
             "WHERE o_orderkey = l_orderkey AND l_quantity < 5 "
             "AND o_totalprice > 300000 ORDER BY 1, 2",
    "left": "SELECT o_orderkey, l_linenumber FROM orders LEFT JOIN "
            "(SELECT l_orderkey, l_linenumber FROM lineitem "
            "WHERE l_quantity < 5) l ON o_orderkey = l_orderkey "
            "WHERE o_totalprice > 300000 ORDER BY 1, 2",
    "semi": "SELECT o_orderkey FROM orders WHERE o_totalprice > 300000 AND "
            "o_orderkey IN (SELECT l_orderkey FROM lineitem "
            "WHERE l_quantity < 5) ORDER BY 1",
    "anti": "SELECT o_orderkey FROM orders WHERE o_totalprice > 300000 AND "
            "o_orderkey NOT IN (SELECT l_orderkey FROM lineitem "
            "WHERE l_quantity < 5) ORDER BY 1",
}


@pytest.mark.parametrize("exchange", ["broadcast", "shuffle"])
@pytest.mark.parametrize("kind", sorted(SPARSE_JOINS))
def test_join_kinds_over_compacted_sides_match_local(env, kind, exchange,
                                                     monkeypatch):
    """Filters leave a few rows in a hundred on both sides, so each side is
    compacted to its live rows before the join (`MppExecutor._compact`)."""
    import galaxysql_tpu.parallel.mpp as M
    inst, s, mesh = env
    if exchange == "shuffle":
        monkeypatch.setattr(M, "BROADCAST_BUILD_LIMIT", 0)
    inst.frag_cache.clear()
    local = s.execute(SPARSE_JOINS[kind])
    assert local.rows
    before = dict(M.EXCHANGE_STATS)
    mpp = run_mpp(inst, s, mesh, SPARSE_JOINS[kind])
    assert_same(rows_of(mpp), local.rows, True)
    assert M.EXCHANGE_STATS["compactions"] - before["compactions"] == 2
    calls = "all_gather_calls" if exchange == "broadcast" else "all_to_all_calls"
    assert M.EXCHANGE_STATS[calls] > before[calls]


def test_semi_anti_join_mpp(env):
    inst, s, mesh = env
    sql = ("SELECT c_custkey FROM customer WHERE c_custkey IN "
           "(SELECT o_custkey FROM orders WHERE o_totalprice > 100) "
           "ORDER BY c_custkey LIMIT 20")
    local = s.execute(sql)
    mpp = run_mpp(inst, s, mesh, sql)
    assert_same(rows_of(mpp), local.rows, True)
    sql2 = ("SELECT count(*) FROM customer WHERE c_custkey NOT IN "
            "(SELECT o_custkey FROM orders)")
    local2 = s.execute(sql2)
    mpp2 = run_mpp(inst, s, mesh, sql2)
    assert_same(rows_of(mpp2), local2.rows, False)


class TestMppOperators:
    """Window / union / distinct / multi-distinct / topn distribute."""

    @pytest.fixture(scope="class")
    def wenv(self):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE d; USE d")
        s.execute("CREATE TABLE w (k VARCHAR(4), v BIGINT, y BIGINT)")
        s.execute("CREATE TABLE w2 (k VARCHAR(4), v BIGINT)")
        rng = np.random.default_rng(5)
        inst.store("d", "w").insert_arrays(
            {"k": np.array(["a", "b", "c"])[rng.integers(0, 3, 3000)],
             "v": rng.integers(0, 50, 3000), "y": rng.integers(0, 100, 3000)},
            inst.tso.next_timestamp())
        inst.store("d", "w2").insert_arrays(
            {"k": np.array(["c", "d", "e"])[rng.integers(0, 3, 500)],
             "v": rng.integers(0, 50, 500)}, inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE w, w2")
        yield inst, s, make_mesh(8)
        s.close()

    CASES = {
        "window_frames": ("SELECT k, v, sum(v) OVER (PARTITION BY k ORDER BY v),"
                          " row_number() OVER (PARTITION BY k ORDER BY v DESC),"
                          " rank() OVER (PARTITION BY k ORDER BY v) FROM w"),
        "window_avg": "SELECT k, avg(y) OVER (PARTITION BY k) FROM w",
        "window_global": "SELECT k, rank() OVER (ORDER BY v) FROM w WHERE v < 5",
        "union_all": ("SELECT k, v FROM w WHERE v < 10 "
                      "UNION ALL SELECT k, v FROM w2 WHERE v > 40"),
        "union_distinct": "SELECT k FROM w UNION SELECT k FROM w2",
        "distinct": "SELECT DISTINCT k FROM w",
        "multi_distinct": ("SELECT k, count(DISTINCT v), sum(y), min(y) FROM w "
                           "GROUP BY k"),
        "topn": "SELECT k, v, y FROM w ORDER BY y DESC, v, k LIMIT 17",
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_operator_case(self, wenv, case):
        inst, s, mesh = wenv
        sql = self.CASES[case]
        local = s.execute(sql)
        mpp = run_mpp(inst, s, mesh, sql, "d")
        ordered = "ORDER BY" in sql and "OVER" not in sql
        assert_same(rows_of(mpp), local.rows, ordered)


class TestMppArchive:
    def test_archive_scan_distributes(self):
        pytest.importorskip("pyarrow")
        from galaxysql_tpu.types import temporal
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE a; USE a")
        s.execute("CREATE TABLE ev (id BIGINT, d DATE, v BIGINT)")
        base = temporal.parse_date("2020-01-01")
        inst.store("a", "ev").insert_arrays(
            {"id": np.arange(2000), "d": base + np.arange(2000) % 100,
             "v": np.arange(2000) * 3}, inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE ev")
        n = inst.archive.archive_older_than(inst, "a", "ev", "d", base + 50)
        assert n > 0
        mesh = make_mesh(8)
        for sql in ("SELECT count(*), sum(v) FROM ev",
                    "SELECT d, count(*) FROM ev GROUP BY d ORDER BY d LIMIT 10"):
            local = s.execute(sql)
            mpp = run_mpp(inst, s, mesh, sql, "a")
            assert_same(rows_of(mpp), local.rows, True)
            # both hot and archive sides contributed
        plan = inst.planner.plan_select("SELECT count(*) FROM ev", "a")
        ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                          archive=inst.archive, archive_instance=inst)
        MppExecutor(ctx, mesh).execute(plan.rel)
        assert any("mpp-scan-archive" in t for t in ctx.trace)
        s.close()


class TestSessionDispatch:
    """The session-level MPP path: MPP actually runs above the row threshold,
    and a non-distributable shape falls back LOUDLY (counter + trace tag)."""

    def test_session_runs_mpp_and_counts(self):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE sd; USE sd")
        s.execute("CREATE TABLE big (k VARCHAR(4), v BIGINT)")
        rng = np.random.default_rng(0)
        inst.store("sd", "big").insert_arrays(
            {"k": np.array(["x", "y", "z"])[rng.integers(0, 3, 50_000)],
             "v": rng.integers(0, 1000, 50_000)}, inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE big")
        s.vars["MPP_MIN_AP_ROWS"] = 1000
        before = inst.counters["mpp_queries"]
        r = s.execute("SELECT k, sum(v), count(*) FROM big GROUP BY k ORDER BY k")
        assert len(r.rows) == 3
        if inst.mesh() is not None:  # 8 virtual devices in tests
            assert inst.counters["mpp_queries"] == before + 1
            assert any(t.startswith("mpp-") for t in s.last_trace)
        s.close()

    def test_session_fallback_is_loud(self, monkeypatch):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE sd2; USE sd2")
        s.execute("CREATE TABLE t (k VARCHAR(4), v BIGINT)")
        rng = np.random.default_rng(1)
        inst.store("sd2", "t").insert_arrays(
            {"k": np.array(["x", "y"])[rng.integers(0, 2, 60_000)],
             "v": np.arange(60_000)}, inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE t")
        s.vars["MPP_MIN_AP_ROWS"] = 1000
        if inst.mesh() is None:
            pytest.skip("no multi-device mesh")
        from galaxysql_tpu.parallel.mpp import MppExecutor as ME

        def boom(self, node):
            raise errors.NotSupportedError("test shape")
        monkeypatch.setattr(ME, "run", boom)
        before = inst.counters["mpp_fallback_local"]
        r = s.execute("SELECT k, sum(v) FROM t GROUP BY k")
        assert sum(x[1] for x in r.rows) == int(np.arange(60_000).sum())
        assert inst.counters["mpp_fallback_local"] == before + 1
        assert any(t.startswith("mpp-fallback") for t in s.last_trace)
        # the counter is visible through information_schema
        rows = s.execute("SELECT value FROM information_schema.engine_counters "
                         "WHERE counter_name = 'mpp_fallback_local'").rows
        assert rows and rows[0][0] >= 1
        s.close()
