"""TPC-H's EXISTS / NOT EXISTS over a fact table (Q4, Q21) on four virtual
devices: through `MppExecutor` and through the local engine against the
benchmark's plain reference (`benchmarks/deployments/tpch_subq.py`), not
against each other; the exchange each join takes once the estimates are
scaled to SF1; the `stage:Join` span's `kind`, `residual` and `matched`;
`MPP_JOIN_STATS` against the plan's joins; and a capacity ladder that starts
where its last climb settled."""

import os

import numpy as np
import pandas as pd
import pytest

import jax

from benchmarks.harness.byname import load_module
from galaxysql_tpu.parallel import mpp as M
from galaxysql_tpu.parallel.mesh import make_mesh
from galaxysql_tpu.plan import logical as L
from galaxysql_tpu.plan.physical import ExecContext
from galaxysql_tpu.plan.rules import estimate_rows
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES
from galaxysql_tpu.utils import tracing

S = 4
SF, SEED = 0.02, 3000000019
LIMIT = M.BROADCAST_BUILD_LIMIT      # as it stands: no test here moves it at SF1
HINT = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "
subq = load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "deployments", "tpch_subq.py"))

# the plan's equi-joins, outermost first, as (kind, residual, exchange at SF1)
PLAN_JOINS = {
    4: [("semi", 0, "shuffle")],
    21: [("anti", 1, "shuffle"), ("semi", 1, "shuffle"),
         ("inner", 0, "broadcast"), ("inner", 0, "broadcast"),
         ("inner", 0, "broadcast")],
}


@pytest.fixture(scope="module")
def env():
    assert len(jax.devices()) >= S, "conftest must provide virtual devices"
    data = tpch.generate(SF, seed=SEED)
    inst = Instance()
    inst._mesh = make_mesh(S)
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    ref = subq.Reference(data)
    yield inst, s, data, {4: ref.q4(), 21: ref.q21()}
    s.close()


@pytest.fixture()
def limit_scaled_to_sf1(monkeypatch):
    """The limit as it stands, in rows of this scale: every join then takes
    the exchange its estimate gives it at SF1."""
    monkeypatch.setattr(M, "BROADCAST_BUILD_LIMIT", int(LIMIT * SF))


def joins_of(node):
    found = [node] if isinstance(node, L.Join) else []
    for child in node.children:
        found += joins_of(child)
    return found


def run_mpp(inst, q):
    inst.frag_cache.clear()     # a warm aggregate would replay and run no join
    plan = inst.planner.plan_select(QUERIES[q], "tpch")
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    tc = tracing.TraceContext(31, node="t")
    with tracing.activate(tc):
        batch = M.MppExecutor(ctx, make_mesh(S)).execute(plan.rel)
    rows = [(r[0], int(r[1])) for r in batch.to_pylist()]
    return rows, [sp for sp in tc.spans
                  if sp.kind == "stage" and sp.name == "mpp:Join"]


def delta(before, stats):
    return {k: stats[k] - before[k] for k in stats if stats[k] != before[k]}


# -- the plain reference against a second, brute-force one -------------------------


def test_reference_q21_equals_the_query_written_out_as_joins(env):
    _inst, _s, data, want = env
    li = pd.DataFrame({k: np.asarray(data["lineitem"][k]) for k in (
        "l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate")})
    o = pd.DataFrame({k: np.asarray(data["orders"][k])
                      for k in ("o_orderkey", "o_orderstatus")})
    s = pd.DataFrame({k: np.asarray(data["supplier"][k])
                      for k in ("s_suppkey", "s_name", "s_nationkey")})
    n = pd.DataFrame({k: np.asarray(data["nation"][k])
                      for k in ("n_nationkey", "n_name")})
    late = li[li.l_receiptdate > li.l_commitdate].reset_index(drop=True)
    l1 = late.assign(rid=late.index) \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(o[o.o_orderstatus == "F"], left_on="l_orderkey",
               right_on="o_orderkey") \
        .merge(n[n.n_name == "SAUDI ARABIA"], left_on="s_nationkey",
               right_on="n_nationkey")
    l2 = l1.merge(li, on="l_orderkey", suffixes=("", "_2"))
    l3 = l1.merge(late, on="l_orderkey", suffixes=("", "_3"))
    exists = set(l2[l2.l_suppkey_2 != l2.l_suppkey].rid)
    not_exists = set(l3[l3.l_suppkey_3 != l3.l_suppkey].rid)
    kept = l1[l1.rid.isin(exists) & ~l1.rid.isin(not_exists)]
    rows = sorted(((k, int(v)) for k, v in kept.groupby("s_name").size().items()),
                  key=lambda r: (-r[1], r[0]))[:100]
    assert want[21] == rows and len(rows) > 3


def test_reference_q4_equals_the_query_written_out_as_a_join(env):
    _inst, _s, data, want = env
    li, o = data["lineitem"], data["orders"]
    late = pd.DataFrame({"ok": np.asarray(li["l_orderkey"])[
        np.asarray(li["l_commitdate"]) < np.asarray(li["l_receiptdate"])]})
    od = np.asarray(o["o_orderdate"])
    quarter = pd.DataFrame({"ok": np.asarray(o["o_orderkey"]),
                            "prio": np.asarray(o["o_orderpriority"])})[
        (od >= subq.days(1993, 7, 1)) & (od < subq.days(1993, 10, 1))]
    hit = quarter.merge(late.drop_duplicates(), on="ok")
    assert want[4] == [(p, int(c)) for p, c in
                       hit.groupby("prio").size().items()]
    assert len(want[4]) == 5


# -- both engines against the plain reference ----------------------------------------


@pytest.mark.parametrize("q", [4, 21])
def test_local_engine_equals_the_plain_reference(env, q):
    inst, s, _data, want = env
    before = int(inst.counters["mpp_queries"])
    rows = [(r[0], int(r[1])) for r in s.execute(HINT + QUERIES[q]).rows]
    assert rows == want[q]
    assert int(inst.counters["mpp_queries"]) == before      # the local engine


@pytest.mark.parametrize("q", [4, 21])
def test_mpp_equals_the_plain_reference_and_says_what_it_ran(
        env, limit_scaled_to_sf1, q):
    inst, _s, _data, want = env
    # the limit as it stands against the estimates scaled to SF1
    plan = inst.planner.plan_select(QUERIES[q], "tpch")
    for join, (kind, _res, exchange) in zip(joins_of(plan.rel), PLAN_JOINS[q]):
        assert join.kind == kind
        build = join.right if kind != "inner" else min(
            (join.left, join.right), key=estimate_rows)
        at_sf1 = estimate_rows(build) / SF
        assert (at_sf1 > LIMIT) == (exchange == "shuffle"), (kind, at_sf1)
    before, before_x = dict(M.MPP_JOIN_STATS), dict(M.EXCHANGE_STATS)
    rows, joins = run_mpp(inst, q)
    assert rows == want[q]
    # the spans: kind, residual flag, the exchange, and what a semi/anti kept
    assert [(sp.attrs["kind"], sp.attrs["residual"], sp.attrs["exchange"])
            for sp in joins] == PLAN_JOINS[q]
    for sp in joins:
        if sp.attrs["kind"] in ("semi", "anti"):
            assert sp.attrs["matched"] == sp.attrs["rows"] > 0
            assert sp.attrs["build_rows"] > sp.attrs["probe_rows"]
        else:
            assert "matched" not in sp.attrs
    # the counters: one a join by kind and exchange, the shuffles' build rows
    want_delta = {}
    for kind, _res, exchange in PLAN_JOINS[q]:
        key = f"{kind}_{exchange}"
        want_delta[key] = want_delta.get(key, 0) + 1
    want_delta["shuffle_build_rows"] = sum(
        sp.attrs["build_rows"] for sp in joins
        if sp.attrs["exchange"] == "shuffle")
    assert delta(before, M.MPP_JOIN_STATS) == want_delta
    # a fact table went through `all_to_all`: every late line (Q4, Q21's l3),
    # every line (Q21's l2)
    lineitem = inst.store("tpch", "lineitem").row_count()
    assert want_delta["shuffle_build_rows"] > (0.5 if q == 4 else 1.5) * lineitem
    assert M.EXCHANGE_STATS["all_to_all_bytes"] > before_x["all_to_all_bytes"]


def test_chip_formulation_equals_the_plain_reference(env, limit_scaled_to_sf1,
                                                     chip_formulation):
    """The sorted join a TPU traces, semi and anti arms under `shard_map`."""
    inst, _s, _data, want = env
    for q in (4, 21):
        rows, joins = run_mpp(inst, q)
        assert rows == want[q]
        assert [sp.attrs["exchange"] for sp in joins] == \
            [x for _k, _r, x in PLAN_JOINS[q]]


# -- a ladder climbs once ------------------------------------------------------------


def test_a_ladder_that_climbed_starts_where_it_settled(env,
                                                      limit_scaled_to_sf1):
    """Q21's anti join probes what the semi join's shuffle dealt by the same
    key: every row of a shard goes to one destination, past the uniform
    share its quota starts from.  The first statement climbs; the next one
    starts on the rung that held."""
    inst, _s, _data, want = env
    M._SETTLED.clear()
    before = dict(M.EXCHANGE_STATS)
    rows, joins = run_mpp(inst, 21)
    anti = joins[0].attrs
    assert rows == want[21] and anti["kind"] == "anti"
    assert anti["retries"] >= 1
    climbed = M.EXCHANGE_STATS["overflow_retries"] - before["overflow_retries"]
    assert climbed == sum(sp.attrs["retries"] for sp in joins)
    assert len(M._SETTLED) == sum(1 for sp in joins if sp.attrs["retries"])

    before = dict(M.EXCHANGE_STATS)
    rows, again = run_mpp(inst, 21)
    assert rows == want[21]
    assert [sp.attrs["retries"] for sp in again] == [0] * len(again)
    for first, second in zip(joins, again):
        for size in ("quota_b", "quota_p", "cap"):
            assert first.attrs.get(size) == second.attrs.get(size)
    assert M.EXCHANGE_STATS["overflow_retries"] == before["overflow_retries"]
    # a ladder that never climbed keeps nothing
    M._SETTLED.clear()
    run_mpp(inst, 4)
    assert not M._SETTLED


def test_settled_ladders_are_bounded(monkeypatch):
    monkeypatch.setattr(M, "_SETTLED", {})
    monkeypatch.setattr(M, "_SETTLED_LIMIT", 3)
    for i in range(5):
        M._ladder_settled(("k", i), (i,), retries=1)
        assert len(M._SETTLED) <= 3
    M._ladder_settled(("never",), (9,), retries=0)
    assert ("never",) not in M._SETTLED
    assert M._SETTLED[("k", 4)] == (4,)


# -- SHOW TRACE over the session -----------------------------------------------------


def test_show_trace_prints_kind_residual_and_matched(env, limit_scaled_to_sf1):
    inst, s, _data, want = env
    inst.config.set_instance("MPP_MIN_AP_ROWS", 1)
    try:
        inst.frag_cache.clear()
        before = int(inst.counters["mpp_queries"])
        s.vars["ENABLE_QUERY_TRACING"] = 1
        rows = [(r[0], int(r[1])) for r in s.execute(HINT + QUERIES[21]).rows]
        assert rows == want[21]
        assert int(inst.counters["mpp_queries"]) == before + 1
        text = "\n".join(str(r[0]) for r in s.execute("SHOW TRACE").rows)
    finally:
        inst.config.set_instance("MPP_MIN_AP_ROWS", 4194304)
        s.vars.pop("ENABLE_QUERY_TRACING", None)
    lines = [ln for ln in text.splitlines() if "mpp:Join" in ln]
    assert len(lines) == 5
    assert "kind=anti" in lines[0] and "residual=1" in lines[0] \
        and "matched=" in lines[0] and "exchange=shuffle" in lines[0]
    assert "kind=semi" in lines[1] and "residual=1" in lines[1] \
        and "matched=" in lines[1]
    assert all("kind=inner" in ln and "residual=0" in ln
               and "matched=" not in ln for ln in lines[2:])
