"""TPC-H's outer, anti and semi joins (Q13, Q22, Q4) through the one-chip
engine: under the chip's formulations and under this CPU's own, against the
benchmark's plain reference (`benchmarks/deployments/tpch_joinkinds.py`), not
against each other; a capacity ladder that climbs once a process and jumps to
the bucket its pairs need; semi and anti joins that enumerate no pair; the
`op:Join` span's `kind`, `residual`, `cap`, `climbs` and `unmatched`/`matched`;
`JOIN_STATS` by kind; and the left join's edges as relations."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.harness.byname import load_module
from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.expr import ir
from galaxysql_tpu.kernels import relational as K
from galaxysql_tpu.plan import planner
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import tracing

SF, SEED = 0.02, 4000000007
HINT = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "
kinds = load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "deployments",
    "tpch_joinkinds.py"))
CHECK = {13: kinds.check_q13, 22: kinds.check_q22, 4: kinds.subq.check_q4}
# the plan's equi-join: (kind, residual); Q13's NOT LIKE reads `orders` only and
# is applied to it before the join (`plan/rules.py:_push_left_join_on`)
PLAN_JOIN = {13: ("left", 0), 22: ("anti", 0), 4: ("semi", 0)}


@pytest.fixture(scope="module")
def env():
    data = tpch.generate(SF, seed=SEED)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    ref = kinds.Reference(data)
    yield inst, s, {13: ref.q13(), 22: ref.q22(), 4: ref.q4()}
    s.close()


@pytest.fixture()
def as_at_sf1(monkeypatch):
    """What the scale hides: at SF1 all three statements plan AP (lanes on the
    device) and no build side here is small enough for a bloom filter or for
    the host to compact, so `orders` and `lineitem` stay on the device."""
    monkeypatch.setattr(planner, "AP_ROW_THRESHOLD", 1)
    monkeypatch.setattr(ops.HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    ops._SETTLED_CAPS.clear()
    yield
    ops._SETTLED_CAPS.clear()


def traced(s, sql):
    """The statement's rows and the `Join` operator spans of its equi-joins
    (Q22's cross join is a `Join` too, and carries no kind)."""
    s.execute("SET ENABLE_QUERY_TRACING = 1")
    rows = s.execute(sql).rows
    spans = [sp for sp in s.last_spans if sp.kind == "operator"
             and sp.name == "Join" and "kind" in sp.attrs]
    return rows, spans


# -- the three queries against the plain reference ---------------------------------


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
@pytest.mark.parametrize("q", [13, 22, 4])
def test_query_equals_the_plain_reference(env, as_at_sf1, request, q, formulation):
    _inst, s, want = env
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")
    CHECK[q](s.execute(HINT + QUERIES[q]).rows, want[q])


def test_the_reference_q13_equals_the_query_written_out_as_a_join(env):
    import pandas as pd
    import re
    _inst, _s, want = env
    data = tpch.generate(SF, seed=SEED)
    c = pd.DataFrame({"ck": np.asarray(data["customer"]["c_custkey"])})
    o = pd.DataFrame({"ok": np.asarray(data["orders"]["o_orderkey"]),
                      "ck": np.asarray(data["orders"]["o_custkey"]),
                      "text": np.asarray(data["orders"]["o_comment"])})
    o = o[[re.search("special.*requests", t) is None for t in o.text]]
    j = c.merge(o, on="ck", how="left")
    c_count = j.groupby("ck").ok.count()
    dist = c_count.value_counts()
    rows = sorted(((int(n), int(k)) for n, k in dist.items()),
                  key=lambda r: (-r[1], -r[0]))
    assert rows == want[13] and rows[0][0] == 0   # a third have no order


# -- the capacity ladder ---------------------------------------------------------


def test_q13_climbs_once_a_process_and_straight_to_the_bucket_its_pairs_need(
        env, as_at_sf1, chip_formulation):
    _inst, s, want = env
    before = dict(ops.JOIN_STATS)
    rows, (join,) = traced(s, HINT + QUERIES[13])
    kinds.check_q13(rows, want[13])          # exact through an overflowed run
    # 3,000 customers probe 30,000 orders: ten pairs a matched probe row, so
    # twice the probe rows (8,192 slots) overflows, and the next rung is the
    # bucket of the pairs counted, not 16,384
    assert join.attrs["climbs"] == 1 and join.attrs["cap"] == 32768
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"] + 1
    pair_programs = {k[2] for k in ops._JIT_CACHE
                     if k[0] == "join_pairs" and isinstance(k[2], int)}
    assert pair_programs == {8192, 32768}

    compiled = ops.COMPILE_STATS["retraces"]
    rows, (join,) = traced(s, HINT + QUERIES[13])
    kinds.check_q13(rows, want[13])
    assert join.attrs["climbs"] == 0 and join.attrs["cap"] == 32768
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"] + 1
    assert ops.COMPILE_STATS["retraces"] == compiled
    assert ops.JOIN_STATS["left"] == before["left"] + 2


def test_q22s_second_execution_compiles_nothing_and_its_join_has_no_ladder(
        env, as_at_sf1, chip_formulation):
    _inst, s, want = env
    before = dict(ops.JOIN_STATS)
    kinds.check_q22(s.execute(HINT + QUERIES[22]).rows, want[22])
    compiled = ops.COMPILE_STATS["retraces"]
    rows, (join,) = traced(s, HINT + QUERIES[22])
    kinds.check_q22(rows, want[22])
    assert ops.COMPILE_STATS["retraces"] == compiled
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"]
    assert (join.attrs["cap"], join.attrs["climbs"]) == (0, 0)
    assert [k[2] for k in ops._JIT_CACHE if k[0] == "join_pairs"] == ["matched"]


@pytest.mark.parametrize("q", [13, 22, 4])
def test_the_counters_by_kind_advance_by_the_plans_joins(
        env, as_at_sf1, chip_formulation, q):
    _inst, s, _want = env
    before = dict(ops.JOIN_STATS)
    rows, (join,) = traced(s, HINT + QUERIES[q])
    kind, residual = PLAN_JOIN[q]
    grown = {k: ops.JOIN_STATS[k] - before[k]
             for k in ("inner", "left", "semi", "anti")}
    assert grown == dict({"inner": 0, "left": 0, "semi": 0, "anti": 0},
                         **{kind: 1})
    assert (join.attrs["kind"], join.attrs["residual"]) == (kind, residual)
    count = "unmatched" if kind == "left" else "matched"
    assert join.attrs[count] > 0
    lines = "\n".join(r[0] for r in s.execute("SHOW TRACE").rows)
    assert f"kind={kind}" in lines and f"{count}={join.attrs[count]}" in lines


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
def test_an_aggregate_over_more_groups_than_estimated_restarts_its_child_once_a_process(
        formulation, request):
    """A group capacity that overflows re-iterates the child (for Q13 that is
    the left join, at SF1 three times a statement while the ladder restarted
    with every one); the rung that held is remembered."""
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")

    class Counted(ops.SourceOp):
        pulls = 0

        def batches(self):
            Counted.pulls += 1
            return super().batches()

    keys = np.arange(5000) % 3000
    key = ir.ColRef("k", dt.BIGINT, None)

    def agg():
        return ops.HashAggOp(Counted([batch("k", keys)]), [("g", key)],
                             [ops.AggCall("count_star", None, "n")],
                             max_groups=1024)
    def rows():
        d = ops.run_to_batch(agg()).to_pydict()
        return sorted(zip(d["g"], d["n"]))
    ops._SETTLED_GROUPS.clear()
    want = sorted((int(k), 2 if k < 2000 else 1) for k in range(3000))
    assert rows() == want
    climbed = Counted.pulls         # the slot table holds 3,000 groups sooner
    assert climbed == (3 if formulation == "chip" else 2)
    assert rows() == want and Counted.pulls == climbed + 1
    assert list(ops._SETTLED_GROUPS.values()) == [1024 << (climbed - 1)]
    ops._SETTLED_GROUPS.clear()


# -- the operator, as relations --------------------------------------------------


def batch(name, values, live=None, valid=None):
    col = Column(jnp.asarray(np.asarray(values, np.int64)),
                 None if valid is None else jnp.asarray(valid), dt.BIGINT, None)
    return ColumnBatch({name: col}, None if live is None else jnp.asarray(live))


def rows_of(op):
    out = []
    for b in op.batches():
        cols = sorted(b.columns)
        d = b.compact().to_pydict()
        out += list(zip(*(d[c] for c in cols)))
    return sorted(out, key=str)


BK, PK = [ir.ColRef("k", dt.BIGINT, None)], [ir.ColRef("a", dt.BIGINT, None)]


def join_of(build, probes, kind, residual=None):
    return ops.HashJoinOp(ops.SourceOp([build]), ops.SourceOp(probes), BK, PK,
                          kind, residual=residual, enable_bloom=False,
                          build_schema={"k": (dt.BIGINT, None)})


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_a_join_without_a_residual_enumerates_no_pair_whatever_the_fan_out(
        kind, chip_formulation):
    rng = np.random.default_rng(33)
    build = batch("k", np.repeat(np.arange(0, 400, 2), 50))  # 50 rows a key
    a = rng.integers(0, 400, 900)
    live = rng.random(900) > 0.2
    tc = tracing.TraceContext(9)
    span = tc.add("Join", kind="operator")
    tc.cursor = span.span_id
    before = dict(ops.JOIN_STATS)
    with tracing.activate(tc):
        got = rows_of(join_of(build, [batch("a", a, live)], kind))
    keep = live & ((a % 2 == 0) == (kind == "semi"))
    assert got == sorted(((int(v),) for v in a[keep]), key=str)
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"]
    assert ops.JOIN_STATS[kind] == before[kind] + 1
    assert ops.JOIN_STATS["expand_levels"] == before["expand_levels"]
    assert ops.JOIN_STATS["probes"] == before["probes"] + 1
    assert span.attrs["kind"] == kind and span.attrs["residual"] == 0
    assert (span.attrs["cap"], span.attrs["climbs"]) == (0, 0)
    assert span.attrs["matched"] == int(keep.sum())
    assert [k[2] for k in ops._JIT_CACHE if k[0] == "join_pairs"] == ["matched"]


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_a_mostly_dead_probe_side_is_searched_in_the_bucket_its_live_rows_fill(
        kind, chip_formulation):
    rng = np.random.default_rng(35)
    build = batch("k", np.repeat(np.arange(0, 9000, 2), 3))
    a = rng.integers(0, 9000, 8192)
    live = rng.random(8192) < 0.05                      # some 400 of 8,192
    tc = tracing.TraceContext(13)
    span = tc.add("Join", kind="operator")
    tc.cursor = span.span_id
    with tracing.activate(tc):
        got = rows_of(join_of(build, [batch("a", a, live)], kind))
    keep = live & ((a % 2 == 0) == (kind == "semi"))
    assert got == sorted(((int(v),) for v in a[keep]), key=str)
    assert span.attrs["matched"] == int(keep.sum())
    # 1,024 probe slots looked up and compared, not 8,192
    (key,) = [k for k in ops._JIT_CACHE if k[0] == "join_pairs"]
    assert key[-1] == 1024
    # a probe side over half full is looked up where it lies
    dense = rng.random(8192) < 0.6
    rows_of(join_of(build, [batch("a", a, dense)], kind))
    assert sorted(str(k[-1]) for k in ops._JIT_CACHE
                  if k[0] == "join_pairs") == ["1024", "None"]


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_two_keys_of_one_hash_are_still_told_apart_without_pairs(
        kind, chip_formulation, monkeypatch):
    # every key collides: the first candidate of a run is then some other key,
    # and the comparison walks the run until it finds the probe row's own
    monkeypatch.setattr(K, "hash_columns", lambda cols: jnp.full(
        cols[0][0].shape[0], 12345, jnp.uint64))
    build = batch("k", np.arange(0, 40, 2))
    a = np.arange(40)
    got = rows_of(join_of(build, [batch("a", a)], kind))
    keep = (a % 2 == 0) == (kind == "semi")
    assert got == sorted(((int(v),) for v in a[keep]), key=str)


@pytest.mark.parametrize("kind", ["inner", "left", "semi", "anti"])
def test_a_first_run_that_overflows_still_answers_exactly_and_settles(
        kind, chip_formulation):
    """Forty pairs a probe row: twice the probe rows never holds them.  With a
    residual the semi and anti arms enumerate pairs too."""
    ops._SETTLED_CAPS.clear()
    build = batch("k", np.repeat(np.arange(30), 40))
    a = np.arange(60)
    residual = None if kind in ("inner", "left") else ir.Call(
        "ge", [ir.ColRef("k", dt.BIGINT, None), ir.Literal(10, dt.BIGINT)],
        dt.BOOL)
    tc = tracing.TraceContext(11)
    span = tc.add("Join", kind="operator")
    tc.cursor = span.span_id
    before = dict(ops.JOIN_STATS)
    with tracing.activate(tc):
        got = rows_of(join_of(build, [batch("a", a)], kind, residual))
    want = {"inner": [(v, v) for v in range(30) for _ in range(40)],
            "left": [(v, v) for v in range(30) for _ in range(40)] +
                    [(v, None) for v in range(30, 60)],
            "semi": [(v,) for v in range(10, 30)],
            "anti": [(v,) for v in list(range(10)) + list(range(30, 60))]}[kind]
    assert got == sorted(want, key=str)
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"] + 1
    assert span.attrs["climbs"] == 1 and span.attrs["cap"] == 2048
    assert span.attrs["residual"] == int(residual is not None)
    if kind == "left":
        assert span.attrs["unmatched"] == 30
    if kind in ("semi", "anti"):
        assert span.attrs["matched"] == len(want)
    # the next execution starts on the rung that held
    tc.cursor = span.span_id
    with tracing.activate(tc):
        assert rows_of(join_of(build, [batch("a", a)], kind, residual)) == got
    assert ops.JOIN_STATS["cap_climbs"] == before["cap_climbs"] + 1
    assert span.attrs["climbs"] == 0 and span.attrs["cap"] == 2048
    ops._SETTLED_CAPS.clear()


def test_a_large_dense_build_side_on_the_device_stays_there(
        chip_formulation, monkeypatch):
    """`orders` at SF1: over `BLOOM_MAX_BUILD` slots, a bucket's worth, more
    than half live.  Its lanes are the scan's own arrays when the join runs;
    a sparse one, a small one or one of an odd size is compacted on the host
    as before, and so is every build side under the CPU's formulation."""
    monkeypatch.setattr(ops.HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    op = join_of(batch("k", [0]), [], "left")
    dense = batch("k", np.arange(2048), np.arange(2048) < 1500)
    assert op._materialize_build([dense]) is dense
    for other in (batch("k", np.arange(2048), np.arange(2048) < 900),   # sparse
                  batch("k", np.arange(1024)),                          # small
                  batch("k", np.arange(2000))):                         # no bucket
        made = op._materialize_build([other])
        assert isinstance(made.columns["k"].data, np.ndarray)
        assert made.capacity == other.num_live()
    # two batches are one build side only after the host has joined them
    assert isinstance(op._materialize_build([dense, dense]).columns["k"].data,
                      np.ndarray)
    monkeypatch.setattr(K, "prefer_scatter", lambda: True)
    assert isinstance(op._materialize_build([dense]).columns["k"].data, np.ndarray)
    # and the join over the batch that stayed answers as the relation does
    monkeypatch.setattr(K, "prefer_scatter", lambda: False)
    got = rows_of(join_of(dense, [batch("a", [3, 1499, 1500, 5000])], "left"))
    assert got == sorted([(3, 3), (1499, 1499), (1500, None), (5000, None)],
                         key=str)


LEFT_EDGES = {
    # name: (build k, build valid, probe a, probe valid, residual on k, want)
    "a probe row with no match": (
        [1, 2], None, [1, 3], None, None, [(1, 1), (3, None)]),
    "every pair of a probe row fails the residual": (
        [1, 1, 2], None, [1, 2], None, 5, [(1, None), (2, None)]),
    "a NULL key on the probe side": (
        [1, 2], None, [1, 0], [True, False], None, [(1, 1), (None, None)]),
    "a NULL key on the build side": (
        [1, 0], [True, False], [1, 0], None, None, [(1, 1), (0, None)]),
}


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
@pytest.mark.parametrize("edge", sorted(LEFT_EDGES))
def test_left_join_edges_as_relations(edge, formulation, request):
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")
    bk, bvalid, pa, pvalid, floor, want = LEFT_EDGES[edge]
    residual = None if floor is None else ir.Call(
        "ge", [ir.ColRef("k", dt.BIGINT, None), ir.Literal(floor, dt.BIGINT)],
        dt.BOOL)
    got = rows_of(join_of(batch("k", bk, valid=bvalid),
                          [batch("a", pa, valid=pvalid)], "left", residual))
    assert got == sorted(want, key=str)


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
def test_left_join_of_an_empty_build_side_null_extends_every_probe_row(
        formulation, request):
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")
    empty = ColumnBatch({"k": Column(np.zeros(0, np.int64), None, dt.BIGINT,
                                     None)}, None)
    got = rows_of(join_of(empty, [batch("a", [4, 5])], "left"))
    assert got == [(4, None), (5, None)]


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
def test_count_of_a_column_skips_the_null_extended_row_and_count_star_does_not(
        formulation, request, as_at_sf1):
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")
    inst = Instance()
    s = Session(inst)
    try:
        s.execute("CREATE DATABASE d")
        s.execute("USE d")
        s.execute("CREATE TABLE a (k INT PRIMARY KEY)")
        s.execute("CREATE TABLE b (id INT PRIMARY KEY, k INT, v INT)")
        s.execute("INSERT INTO a VALUES (1), (2), (3)")
        s.execute("INSERT INTO b VALUES (1, 1, 10), (2, 1, 2), (3, 2, 1), "
                  "(4, NULL, 9)")
        rows = s.execute(
            HINT + "select a.k, count(b.id), count(*) from a left join b "
                   "on a.k = b.k and b.v > 5 group by a.k order by a.k").rows
        # 1: one pair passes; 2: its only pair fails the ON clause; 3: no pair
        assert [tuple(int(x) for x in r) for r in rows] == \
            [(1, 1, 1), (2, 0, 1), (3, 0, 1)]
    finally:
        s.close()


# -- a LEFT join's ON clause: what reads the null-supplying side alone goes below --


@pytest.fixture(scope="module")
def ab():
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE d")
    s.execute("USE d")
    s.execute("CREATE TABLE a (k INT PRIMARY KEY, w INT)")
    s.execute("CREATE TABLE b (id INT PRIMARY KEY, k INT, v INT)")
    s.execute("INSERT INTO a VALUES (1, 10), (2, 20), (3, 30), (4, NULL)")
    s.execute("INSERT INTO b VALUES (1, 1, 10), (2, 1, 2), (3, 2, NULL), "
              "(4, 3, 7), (5, NULL, 9), (6, 4, 50)")
    yield s
    s.close()


ON_CLAUSES = {
    # name: (conjunct, a Filter goes below the join, the join keeps a
    # residual, [(a.k, b.id)] ordered)
    "a predicate on the right side, NULL in its column": (
        "b.v > 5", True, False, [(1, 1), (2, None), (3, 4), (4, 6)]),
    "a right side left empty": (
        "b.v > 100", True, False, [(1, None), (2, None), (3, None), (4, None)]),
    "a conjunct that reads both sides": (
        "b.v > a.w", False, True, [(1, None), (2, None), (3, None), (4, None)]),
    "a conjunct on the preserved side": (
        "a.k > 1", False, True, [(1, None), (2, 3), (3, 4), (4, 6)]),
    "one of each": (
        "b.v > 5 and b.v < a.w", True, True,
        [(1, None), (2, None), (3, 4), (4, None)]),
}


@pytest.mark.parametrize("formulation", ["chip", "cpu"])
@pytest.mark.parametrize("case", sorted(ON_CLAUSES))
def test_left_join_on_clause(ab, as_at_sf1, request, case, formulation):
    if formulation == "chip":
        request.getfixturevalue("chip_formulation")
    conjunct, below, residual, want = ON_CLAUSES[case]
    sql = ("select a.k, b.id from a left join b on a.k = b.k and " + conjunct +
           " order by a.k, b.id")
    plan = "\n".join(r[0] for r in ab.execute("EXPLAIN " + sql).rows)
    join = next(ln for ln in plan.split("\n") if "Join(left" in ln)
    assert ("Filter(" in plan.split(join, 1)[1]) == below, plan
    assert ("residual=" in join) == residual, plan
    rows = ab.execute(HINT + sql).rows
    assert [(int(k), None if i is None else int(i)) for k, i in rows] == want
