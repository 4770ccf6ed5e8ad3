"""Kind `tpch_joinkinds` and the left/anti/semi cell's readers, without a chip:
the plain reference on a data set small enough to answer by hand, the
comparisons that decide `correct`, and the two counter readers on a run built
by hand and on a commit whose program keeps no such counters."""

import os
import types

import numpy as np
import pytest

from benchmarks.harness import local_joins
from benchmarks.harness.byname import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
kinds = load_module(os.path.join(ROOT, "deployments", "tpch_joinkinds.py"))
D = kinds.subq.days


def reader(name):
    return load_module(os.path.join(ROOT, "metrics", name + ".py"))


def by_hand():
    """Eight customers.  1: no order, balance under the average.  2: two
    orders.  3: its only order reads `special ... requests`, so it counts 0.
    4: a code outside the list.  5: a negative balance, outside the average.
    6: over the average, and has orders (one of them matches the LIKE, one
    has the two words the other way round).  7, 8: over the average and
    dormant.  Average of the positive balances of the seven codes:
    (100 + 300 + 500 + 700 + 950 + 600) / 6 = 525."""
    customers = [  # custkey, phone, balance
        (1, "13-100-1000", 100.00), (2, "31-100-1000", 300.00),
        (3, "13-100-1001", 500.00), (4, "99-100-1000", 900.00),
        (5, "17-100-1000", -50.00), (6, "23-100-1000", 700.00),
        (7, "23-100-1001", 950.00), (8, "13-100-1002", 600.00)]
    orders = [  # orderkey, custkey, comment
        (1, 2, "carefully final deposits"),
        (2, 6, "slyly special even requests"),
        (3, 6, "requests special foxes"),
        (4, 2, "special ideas pending"),
        (5, 3, "special requests ironic")]
    ck, phone, bal = (np.array(c) for c in zip(*customers))
    ok, o_ck, text = (np.array(c) for c in zip(*orders))
    in_q = D(1993, 8, 1)
    return {
        "customer": {"c_custkey": ck, "c_phone": phone, "c_acctbal": bal},
        "orders": {"o_orderkey": ok, "o_custkey": o_ck, "o_comment": text,
                   "o_orderdate": np.array([in_q, in_q, D(1993, 10, 1), in_q,
                                            D(1993, 7, 1)]),
                   "o_orderstatus": np.array(["F"] * 5),
                   "o_orderpriority": np.array(["1-URGENT", "1-URGENT",
                                                "2-HIGH", "5-LOW", "2-HIGH"])},
        # order 1 late, 2 on time, 3 late but after the quarter, 4 and 5 late
        "lineitem": {"l_orderkey": np.array([1, 2, 3, 4, 5, 5]),
                     "l_suppkey": np.array([1, 1, 1, 1, 1, 1]),
                     "l_commitdate": np.array([10, 10, 10, 10, 10, 10]),
                     "l_receiptdate": np.array([11, 10, 11, 12, 9, 11])},
        "supplier": {"s_suppkey": np.array([1]),
                     "s_name": np.array(["Supplier#1"]),
                     "s_nationkey": np.array([20])},
        "nation": {"n_nationkey": np.array([20]),
                   "n_name": np.array(["SAUDI ARABIA"])},
    }


def test_the_reference_answers_a_data_set_worked_out_by_hand():
    ref = kinds.Reference(by_hand())
    # six customers count no order (3's only one matches the LIKE), customer 2
    # counts two, customer 6 one: ordered custdist desc, c_count desc
    assert ref.q13() == [(0, 6), (2, 1), (1, 1)]
    # over 525.00 are 6 (has orders), 7 and 8; 4 is over it in another code
    assert ref.q22() == [("13", 1, 60000), ("23", 1, 95000)]
    assert ref.q4() == [("1-URGENT", 1), ("2-HIGH", 1), ("5-LOW", 1)]


def test_the_reference_says_so_when_a_balance_equals_the_average():
    data = by_hand()
    bal = data["customer"]["c_acctbal"].copy()
    bal[[6, 7]] = 900.00, 500.00       # customers 7 and 8
    data["customer"]["c_acctbal"] = bal
    # (100 + 300 + 500 + 700 + 900 + 500) / 6 = 500.00: customers 3 and 8 sit
    # on it, where the engine's DECIMAL(.., 6) average could compare either way
    with pytest.raises(AssertionError, match="within 0.000001 of the average"):
        kinds.Reference(data).q22()
    bal[7] = 500.06                    # the average moves to 500.01
    assert kinds.Reference(data).q22() == [("13", 1, 50006), ("23", 1, 90000)]


def test_the_comparisons_refuse_a_wrong_count_sum_order_or_row():
    ref13 = [(0, 6), (2, 1), (1, 1)]
    kinds.check_q13([(str(a), str(b)) for a, b in ref13], ref13)
    for wrong in (ref13[:2], [(0, 6), (1, 1), (2, 1)], [(0, 5)] + ref13[1:]):
        with pytest.raises(AssertionError):
            kinds.check_q13(wrong, ref13)
    ref22 = [("13", 1, 60000), ("23", 2, 9007199254740993)]
    # the wire's float64 rendering of a sum past 2^53 cents is held to one ulp
    kinds.check_q22([("13", "1", "600.00"), ("23", "2", "90071992547409.92")],
                    ref22)
    for wrong in ([("13", "1", "600.00")],
                  [("13", "1", "600.01"), ("23", "2", "90071992547409.93")],
                  [("13", "2", "600.00"), ("23", "2", "90071992547409.93")],
                  [("23", "2", "90071992547409.93"), ("13", "1", "600.00")]):
        with pytest.raises(AssertionError):
            kinds.check_q22(wrong, ref22)


def test_the_kind_runs_on_kind_tpchs_load_with_its_own_reference_and_checks():
    assert kinds.load is kinds.tpch.load
    assert kinds.tpch.Reference is kinds.Reference
    assert kinds.tpch.Deployment is kinds.Deployment
    assert set(kinds.tpch.CHECKS) == {"q4", "q13", "q22"}
    assert kinds.tpch.CHECKS["q4"] is kinds.subq.check_q4
    # copies of its own: kinds `tpch` and `tpch_subq` keep theirs
    tpch = load_module(os.path.join(ROOT, "deployments", "tpch.py"))
    assert tpch.Reference is not kinds.Reference and "q3" in tpch.CHECKS
    assert set(kinds.subq.tpch.CHECKS) == {"q4", "q21"}


STATS = {"inner": 0, "left": 4, "semi": 4, "anti": 4, "cap_climbs": 1}


def run_like(before=STATS, after=None, attempted=9):
    return types.SimpleNamespace(
        trace=None, state={}, out_dir="/nonexistent",
        deployment=types.SimpleNamespace(window_joins=(before, after)),
        traffic={"warm_executions": 2,
                 "statements": [{"name": "q13"}, {"name": "q22"},
                                {"name": "q4"}]},
        window={"attempted": attempted, "latencies_s": {
            "q13": [0.2, 0.4, 0.3], "q22": [0.1, 0.3, 0.2],
            "q4": [1.0, 3.0, 2.0]}})


def test_the_counter_readers_take_the_windows_growth_a_statement():
    after = dict(STATS, left=7, semi=7, anti=7)
    run = run_like(after=after)           # three whole rounds in the window
    assert reader("ap_outer_semi_anti_joins_per_stmt").read(run) == 1.0
    assert reader("ap_join_cap_climbs_per_stmt").read(run) == 0.0
    # a statement whose join took another path, a ladder that climbs each time
    run = run_like(after=dict(after, anti=6, cap_climbs=4))
    assert reader("ap_outer_semi_anti_joins_per_stmt").read(run) == 8 / 9
    assert reader("ap_join_cap_climbs_per_stmt").read(run) == 3 / 9
    assert reader("ap_q13_s").read(run) == 0.3
    assert reader("ap_q22_s").read(run) == 0.2
    assert reader("ap_q4_s").read(run) == 2.0


def test_the_deployment_takes_the_counters_where_the_driver_takes_the_engines(
        monkeypatch):
    from galaxysql_tpu.exec import operators as ops
    served = types.SimpleNamespace(instance=types.SimpleNamespace(
        counters={"mpp_queries": 0, "mpp_fallback_local": 0}))
    dep = kinds.Deployment(served, {"database": "tpch", "engine": "local"},
                           None, {}, {})
    monkeypatch.setattr(ops, "JOIN_STATS", dict(ops.JOIN_STATS, **STATS))
    before = dep.engine_counts()
    ops.JOIN_STATS.update(left=5, cap_climbs=2)
    after = dep.engine_counts()
    dep.check_engine(before, after, 3)
    assert dep.window_joins == (STATS, dict(STATS, left=5, cap_climbs=2))
    served.instance.counters["mpp_queries"] = 1
    with pytest.raises(AssertionError, match="took the MPP engine"):
        dep.check_engine(before, dep.engine_counts(), 3)


@pytest.mark.parametrize("name", ["ap_outer_semi_anti_joins_per_stmt",
                                  "ap_join_cap_climbs_per_stmt"])
def test_the_counter_readers_return_none_on_a_commit_without_the_counters(
        monkeypatch, name):
    from galaxysql_tpu.exec import operators as ops
    monkeypatch.setattr(ops, "JOIN_STATS", {"probes": 0, "search_levels": 0})
    assert local_joins.join_stats() is None
    served = types.SimpleNamespace(instance=types.SimpleNamespace(
        counters={"mpp_queries": 0, "mpp_fallback_local": 0}))
    dep = kinds.Deployment(served, {"database": "tpch", "engine": "local"},
                           None, {}, {})
    counts = dep.engine_counts()
    dep.check_engine(counts, counts, 3)
    assert dep.window_joins == (None, None)
    assert reader(name).read(run_like(before=None, after=None)) is None
    monkeypatch.delattr(ops, "JOIN_STATS")
    assert local_joins.join_stats() is None
    # a deployment kind that keeps no snapshot (another kind's) reads None too
    run = run_like()
    del run.deployment.window_joins
    assert reader(name).read(run) is None


def test_the_query_files_are_the_programs_texts():
    from galaxysql_tpu.storage.tpch_queries import QUERIES
    for q in (4, 13, 22):
        with open(os.path.join(ROOT, "queries", f"tpch_q{q}.sql")) as f:
            assert f.read().strip() == QUERIES[q].strip()
