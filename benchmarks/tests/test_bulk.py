"""Kind `tpch_bulk` and the scale cell's readers, without a chip: its numpy
reference against kind `tpch`'s pandas one on a data set small enough to answer
by hand and on a generated one, the refusal on a program that has no array
generator, and the five readers on a run built by hand and on a program that
keeps none of the counters.  (The cell's dry runs come with
`test_dry_run.py`'s list.)"""

import os
import types

import numpy as np
import pytest

from benchmarks.harness.byname import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
bulk = load_module(os.path.join(ROOT, "deployments", "tpch_bulk.py"))
plain = load_module(os.path.join(ROOT, "deployments", "tpch.py"))
D = plain.days


def reader(name):
    return load_module(os.path.join(ROOT, "metrics", name + ".py"))


def by_hand():
    """Customers 1 (BUILDING, INDIA), 2 (BUILDING, FRANCE), 3 (MACHINERY,
    INDIA).  Orders: 1 of customer 1 before the date, in 1994; 5 of customer 2
    before the date, in 1993; 9 of customer 3 before the date, in 1994; 13 of
    customer 1 after the date.  Suppliers 1 (INDIA), 2 (FRANCE)."""
    before, after = D(1995, 3, 15) - 1, D(1995, 3, 15) + 1
    lines = [  # orderkey, suppkey, price, discount, shipdate
        (1, 1, 100.00, 0.10, after),     # Q3: 900,000; Q5 INDIA: 900,000
        (1, 2, 50.00, 0.00, after),      # Q3: 500,000; supplier FRANCE: not Q5
        (1, 1, 70.00, 0.05, before),     # shipped before: not Q3; Q5: 665,000
        (5, 2, 20.00, 0.00, after),      # Q3: 200,000; order of 1993: not Q5
        (9, 1, 30.00, 0.00, after),      # MACHINERY: not Q3; Q5 INDIA: 300,000
        (13, 1, 10.00, 0.00, after)]     # ordered after the date: neither
    ok, sk, price, disc, ship = (np.array(c) for c in zip(*lines))
    n = len(lines)
    return {
        "lineitem": {"l_orderkey": ok, "l_suppkey": sk, "l_extendedprice": price,
                     "l_discount": disc, "l_shipdate": ship,
                     "l_quantity": np.ones(n), "l_tax": np.zeros(n),
                     "l_returnflag": np.array(["N"] * n),
                     "l_linestatus": np.array(["O"] * n)},
        "orders": {"o_orderkey": np.array([1, 5, 9, 13]),
                   "o_custkey": np.array([1, 2, 3, 1]),
                   "o_orderdate": np.array([D(1994, 6, 1), D(1993, 6, 1),
                                            D(1994, 12, 31), D(1995, 3, 15)]),
                   "o_shippriority": np.array([0, 0, 0, 0])},
        "customer": {"c_custkey": np.array([1, 2, 3]),
                     "c_nationkey": np.array([8, 6, 8]),
                     "c_mktsegment": np.array(["BUILDING", "BUILDING",
                                               "MACHINERY"])},
        "supplier": {"s_suppkey": np.array([1, 2]),
                     "s_nationkey": np.array([8, 6])},
        "nation": {"n_nationkey": np.array([6, 8]),
                   "n_name": np.array(["FRANCE", "INDIA"]),
                   "n_regionkey": np.array([3, 2])},
        "region": {"r_regionkey": np.array([2, 3]),
                   "r_name": np.array(["ASIA", "EUROPE"])},
    }


def test_the_reference_answers_a_data_set_worked_out_by_hand():
    ref = bulk.Reference(by_hand())
    assert ref.q3() == {(1, D(1994, 6, 1), 0): 1_400_000,
                        (5, D(1993, 6, 1), 0): 200_000}
    assert ref.q5() == [("INDIA", plain.dec(1_865_000, 4))]


def generated(sf, seed):
    from galaxysql_tpu.storage import tpch
    return tpch.generate_arrays(sf, seed)


@pytest.mark.parametrize("data", [by_hand, lambda: generated(0.01, 5),
                                  lambda: generated(0.02, 3141592653)],
                         ids=["by_hand", "sf0.01", "sf0.02"])
def test_kind_tpch_bulk_answers_as_kind_tpch_does(data, monkeypatch):
    data = data()
    monkeypatch.setattr(bulk, "BLOCK", 1000)       # several blocks of lineitem
    ours, theirs = bulk.Reference(data), plain.Reference(data)
    q3, q5 = ours.q3(), ours.q5()
    assert q3 == theirs.q3() and len(q3) > 0
    assert q5 == theirs.q5() and len(q5) > 0
    assert all(type(k) is tuple and type(v) is int for k, v in q3.items())
    assert bulk.CHECKS is plain.CHECKS or set(bulk.CHECKS) == set(plain.CHECKS)


def test_load_stops_at_once_on_a_program_without_generate_arrays(monkeypatch):
    from galaxysql_tpu.storage import tpch
    monkeypatch.delattr(tpch, "generate_arrays")
    monkeypatch.setattr(tpch, "generate", lambda *a, **k: pytest.fail(
        "the list generator was called"))
    config = {"scale_factor": 10, "dry_run_scale_factor": 0.02}
    with pytest.raises(SystemExit) as stop:
        bulk.load(None, config, 1, False)         # nothing is served or made
    assert "generate_arrays" in str(stop.value)


def a_run(timings):
    return types.SimpleNamespace(
        deployment=types.SimpleNamespace(timings=timings),
        window={"latencies_s": {"q3": [1.0]}, "attempted": 1})


def test_the_set_up_readers_read_the_kinds_timings():
    run = a_run({"generate_s": 31.5, "load_s": 88.25, "analyze_s": 14.0})
    assert reader("setup_generate_s").read(run) == 31.5
    assert reader("setup_load_s").read(run) == 88.25
    bare = types.SimpleNamespace(deployment=types.SimpleNamespace(), window={})
    assert reader("setup_generate_s").read(bare) is None
    assert reader("setup_load_s").read(bare) is None


def test_load_us_per_row_reads_the_programs_counter(monkeypatch):
    from galaxysql_tpu.storage import table_store
    monkeypatch.setattr(table_store, "LOAD_STATS", {
        "calls": 8, "rows": 4_000_000, "bytes": 1, "encode_s": 1.0,
        "route_s": 0.5, "append_s": 0.5})
    assert reader("load_us_per_row").read(a_run({})) == 0.5
    monkeypatch.setitem(table_store.LOAD_STATS, "rows", 0)
    assert reader("load_us_per_row").read(a_run({})) is None
    monkeypatch.delattr(table_store, "LOAD_STATS")      # the parent's program
    assert reader("load_us_per_row").read(a_run({})) is None


def test_the_lane_cache_readers_read_the_caches_own_counts(monkeypatch):
    from galaxysql_tpu.exec import device_cache
    cache = device_cache.DeviceCache(budget_bytes=1000)
    monkeypatch.setattr(device_cache, "GLOBAL_DEVICE_CACHE", cache)
    store = types.SimpleNamespace(uid=20_000_001)
    for i in range(3):
        cache.get_lane(store, 0, f"c{i}", 1, np.zeros(100, np.int32))
    run = a_run({})
    assert reader("ap_lane_cache_bytes").read(run) == 800
    assert reader("ap_lane_cache_evictions").read(run) == 1
    # before the window's answers exist there is nothing to report
    idle = types.SimpleNamespace(deployment=None, window={})
    assert reader("ap_lane_cache_bytes").read(idle) is None
    assert reader("ap_lane_cache_evictions").read(idle) is None
    # a program whose cache does not count evictions: the reader has nothing
    monkeypatch.setattr(device_cache, "GLOBAL_DEVICE_CACHE",
                        types.SimpleNamespace(_bytes=800))
    assert reader("ap_lane_cache_bytes").read(run) == 800
    assert reader("ap_lane_cache_evictions").read(run) is None
