"""Kind `tpch_subq` and the semi/anti cell's readers, without a chip: the plain
reference on a data set small enough to answer by hand, the comparisons that
decide `correct`, and the two counter readers on a run built by hand and on a
commit whose program keeps no `MPP_JOIN_STATS`."""

import os
import types

import numpy as np
import pytest

from benchmarks.harness import mpp_joins
from benchmarks.harness.byname import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
subq = load_module(os.path.join(ROOT, "deployments", "tpch_subq.py"))
D = subq.days


def reader(name):
    return load_module(os.path.join(ROOT, "metrics", name + ".py"))


def by_hand():
    """Five orders.  1: suppliers 1 (late) and 2: supplier 1 waits.  2: 1 and
    3 both late: nobody waits alone.  3: supplier 1 alone, late twice: no
    other supplier.  4: as order 1 but still open.  5: supplier 2 (of another
    nation) late, 1 on time: 2 would wait, and is not Saudi."""
    lines = [  # orderkey, suppkey, commit, receipt
        (1, 1, 10, 11), (1, 2, 10, 10),
        (2, 1, 10, 12), (2, 3, 10, 11),
        (3, 1, 10, 11), (3, 1, 10, 12),
        (4, 1, 10, 11), (4, 2, 10, 9),
        (5, 2, 10, 11), (5, 1, 10, 10)]
    ok, sk, commit, receipt = (np.array(c) for c in zip(*lines))
    in_q = D(1993, 8, 1)
    return {
        "lineitem": {"l_orderkey": ok, "l_suppkey": sk,
                     "l_commitdate": commit, "l_receiptdate": receipt},
        "orders": {"o_orderkey": np.arange(1, 6),
                   "o_orderdate": np.array([in_q, in_q, D(1993, 10, 1), in_q,
                                            D(1993, 7, 1)]),
                   "o_orderstatus": np.array(["F", "F", "F", "O", "F"]),
                   "o_orderpriority": np.array(["1-URGENT", "1-URGENT",
                                                "2-HIGH", "5-LOW", "2-HIGH"])},
        "supplier": {"s_suppkey": np.array([1, 2, 3]),
                     "s_name": np.array(["Supplier#1", "Supplier#2",
                                         "Supplier#3"]),
                     "s_nationkey": np.array([20, 7, 20])},
        "nation": {"n_nationkey": np.array([7, 20]),
                   "n_name": np.array(["GERMANY", "SAUDI ARABIA"])},
    }


def test_the_reference_answers_a_data_set_worked_out_by_hand():
    ref = subq.Reference(by_hand())
    # order 3 is dated the day after the quarter, order 5 its first day
    assert ref.q4() == [("1-URGENT", 2), ("2-HIGH", 1), ("5-LOW", 1)]
    # order 1 alone: in order 5 the supplier who waits is not Saudi
    assert ref.q21() == [("Supplier#1", 1)]


def test_a_supplier_late_twice_in_one_order_counts_twice():
    data = by_hand()
    li = data["lineitem"]
    for k, v in zip(("l_orderkey", "l_suppkey", "l_commitdate",
                     "l_receiptdate"), (1, 1, 10, 15)):
        li[k] = np.append(li[k], v)
    assert subq.Reference(data).q21() == [("Supplier#1", 2)]


def test_the_comparisons_refuse_a_wrong_count_order_or_row():
    ref4 = [("1-URGENT", 2), ("2-HIGH", 1)]
    subq.check_q4([("1-URGENT", "2"), ("2-HIGH", "1")], ref4)
    for wrong in ([("1-URGENT", "2")], [("1-URGENT", "3"), ("2-HIGH", "1")],
                  [("2-HIGH", "1"), ("1-URGENT", "2")]):
        with pytest.raises(AssertionError):
            subq.check_q4(wrong, ref4)
    ref21 = [("Supplier#9", 4), ("Supplier#1", 3), ("Supplier#2", 3)]
    subq.check_q21([(n, str(c)) for n, c in ref21], ref21)
    for wrong in (ref21[:2], [ref21[0], ref21[2], ref21[1]],
                  [("Supplier#9", 5)] + ref21[1:]):
        with pytest.raises(AssertionError):
            subq.check_q21(wrong, ref21)


def test_the_kind_runs_on_kind_tpchs_load_and_engine_check():
    assert subq.load is subq.tpch.load and subq.Deployment is subq.tpch.Deployment
    assert subq.tpch.Reference is subq.Reference
    assert set(subq.tpch.CHECKS) == {"q4", "q21"}
    # a copy of its own: kind `tpch` itself keeps its reference and checks
    tpch = load_module(os.path.join(ROOT, "deployments", "tpch.py"))
    assert tpch.Reference is not subq.Reference and "q3" in tpch.CHECKS


def run_like(attempted=6, warm=1):
    return types.SimpleNamespace(
        trace=None, state={}, out_dir="/nonexistent",
        traffic={"warm_executions": warm,
                 "statements": [{"name": "q4"}, {"name": "q21"}]},
        window={"attempted": attempted, "latencies_s": {
            "q4": [0.2, 0.4, 0.3], "q21": [1.0, 3.0, 2.0]}})


def test_the_counter_readers_divide_by_the_statements_sent(monkeypatch):
    from galaxysql_tpu.parallel import mpp
    stats = dict.fromkeys(mpp.MPP_JOIN_STATS, 0)
    stats.update(semi_shuffle=8, anti_shuffle=4, inner_broadcast=12,
                 semi_broadcast=0, shuffle_build_rows=8 * 4_000_000)
    monkeypatch.setattr(mpp, "MPP_JOIN_STATS", stats)
    run = run_like()             # 6 in the window + one warm round of 2
    assert mpp_joins.joins_of_kinds(stats, ("semi", "anti")) == 12
    assert reader("mpp_semi_anti_joins_per_stmt").read(run) == 1.5
    assert reader("mpp_shuffle_build_rows_per_stmt").read(run) == 4_000_000
    assert reader("ap_q4_s").read(run) == 0.3
    assert reader("ap_q21_s").read(run) == 2.0


@pytest.mark.parametrize("name", ["mpp_semi_anti_joins_per_stmt",
                                  "mpp_shuffle_build_rows_per_stmt"])
def test_the_counter_readers_return_none_on_a_commit_without_the_counter(
        monkeypatch, name):
    from galaxysql_tpu.parallel import mpp
    monkeypatch.delattr(mpp, "MPP_JOIN_STATS")
    assert mpp_joins.join_stats() is None
    assert reader(name).read(run_like()) is None


def test_the_query_files_are_the_programs_texts():
    from galaxysql_tpu.storage.tpch_queries import QUERIES
    for q in (4, 21):
        with open(os.path.join(ROOT, "queries", f"tpch_q{q}.sql")) as f:
            assert f.read().strip() == QUERIES[q].strip()
