"""`storage/tpch.py:generate_arrays` against the list generator it replaced, which
lives on here as the plain reference: every value of every column for the same
`(sf, seed)`, so that a seed names the same data (and the same capacity buckets
and cached programs) as before."""

from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np
import pytest

from galaxysql_tpu.chunk.batch import EncodedStrings
from galaxysql_tpu.storage import tpch


def _list_comments(rng: np.random.Generator, n: int) -> List[str]:
    w = tpch._COMMENT_WORDS[rng.integers(0, len(tpch._COMMENT_WORDS), (n, 3))]
    return [" ".join(r) for r in w]


def list_generate(sf: float, seed: int = 19920101) -> Dict[str, Dict[str, list]]:
    """All eight tables at scale factor `sf` as column dicts of Python values: the
    generator as it stood before `generate_arrays`, kept here as the plain
    reference (a list a column, two dict loops over every line)."""
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict[str, list]] = {}

    out["region"] = {
        "r_regionkey": list(range(5)),
        "r_name": tpch.REGIONS,
        "r_comment": _list_comments(rng, 5),
    }
    out["nation"] = {
        "n_nationkey": list(range(25)),
        "n_name": [n for n, _ in tpch.NATIONS],
        "n_regionkey": [r for _, r in tpch.NATIONS],
        "n_comment": _list_comments(rng, 25),
    }

    n_supp = max(int(10_000 * sf), 50)
    supp_keys = np.arange(1, n_supp + 1)
    out["supplier"] = {
        "s_suppkey": supp_keys.tolist(),
        "s_name": [f"Supplier#{k:09d}" for k in supp_keys],
        "s_address": [f"addr{k}" for k in supp_keys],
        "s_nationkey": rng.integers(0, 25, n_supp).tolist(),
        "s_phone": [f"{10+k%25}-{k%900+100}-{k%9000+1000}" for k in supp_keys],
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2).tolist(),
        "s_comment": _list_comments(rng, n_supp),
    }

    n_part = max(int(200_000 * sf), 200)
    part_keys = np.arange(1, n_part + 1)
    name_ix = rng.integers(0, len(tpch.P_NAME_WORDS), (n_part, 5))
    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    out["part"] = {
        "p_partkey": part_keys.tolist(),
        "p_name": [" ".join(tpch.P_NAME_WORDS[j] for j in row) for row in name_ix],
        "p_mfgr": [f"Manufacturer#{m}" for m in mfgr],
        "p_brand": [f"Brand#{b}" for b in brand],
        "p_type": [f"{tpch.TYPE_S1[a]} {tpch.TYPE_S2[b]} {tpch.TYPE_S3[c]}"
                   for a, b, c in zip(rng.integers(0, 6, n_part),
                                      rng.integers(0, 5, n_part),
                                      rng.integers(0, 5, n_part))],
        "p_size": rng.integers(1, 51, n_part).tolist(),
        "p_container": [f"{tpch.CONTAINERS1[a]} {tpch.CONTAINERS2[b]}"
                        for a, b in zip(rng.integers(0, 5, n_part),
                                        rng.integers(0, 8, n_part))],
        "p_retailprice": np.round(
            900 + (part_keys % 1000) / 10 + 100 * (part_keys % 10), 2).tolist(),
        "p_comment": _list_comments(rng, n_part),
    }

    n_ps = n_part * 4
    ps_part = np.repeat(part_keys, 4)
    ps_supp = np.zeros(n_ps, dtype=np.int64)
    for j in range(4):
        ps_supp[j::4] = (ps_part[j::4] + (j * (n_supp // 4 + (ps_part[j::4] - 1)
                                               % (n_supp // 4)))) % n_supp + 1
    out["partsupp"] = {
        "ps_partkey": ps_part.tolist(),
        "ps_suppkey": ps_supp.tolist(),
        "ps_availqty": rng.integers(1, 10_000, n_ps).tolist(),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n_ps), 2).tolist(),
        "ps_comment": _list_comments(rng, n_ps),
    }

    n_cust = max(int(150_000 * sf), 150)
    cust_keys = np.arange(1, n_cust + 1)
    out["customer"] = {
        "c_custkey": cust_keys.tolist(),
        "c_name": [f"Customer#{k:09d}" for k in cust_keys],
        "c_address": [f"addr{k}" for k in cust_keys],
        "c_nationkey": rng.integers(0, 25, n_cust).tolist(),
        "c_phone": [f"{10+k%25}-{k%900+100}-{k%9000+1000}" for k in cust_keys],
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2).tolist(),
        "c_mktsegment": [tpch.SEGMENTS[i] for i in rng.integers(0, 5, n_cust)],
        "c_comment": _list_comments(rng, n_cust),
    }

    n_ord = n_cust * 10
    ord_keys = np.arange(1, n_ord + 1) * 4 - 3  # sparse keys like dbgen
    o_date = tpch._EPOCH_1992 + rng.integers(0, tpch._ORDER_DATE_RANGE, n_ord)
    # only ~2/3 of customers have orders (spec): map to custkey % 3 != 0
    o_cust = rng.integers(1, n_cust + 1, n_ord)
    o_cust = o_cust - (o_cust % 3 == 0)
    o_cust = np.where(o_cust == 0, 1, o_cust)
    out["orders"] = {
        "o_orderkey": ord_keys.tolist(),
        "o_custkey": o_cust.tolist(),
        "o_orderstatus": ["F"] * n_ord,  # fixed after lineitem below
        "o_totalprice": np.zeros(n_ord).tolist(),
        "o_orderdate": o_date.tolist(),
        "o_orderpriority": [tpch.PRIORITIES[i] for i in rng.integers(0, 5, n_ord)],
        "o_clerk": [f"Clerk#{i:09d}" for i in rng.integers(1, max(int(sf * 1000), 10),
                                                           n_ord)],
        "o_shippriority": [0] * n_ord,
        "o_comment": _list_comments(rng, n_ord),
    }

    # lineitem: 1-7 lines per order
    lines_per = rng.integers(1, 8, n_ord)
    n_li = int(lines_per.sum())
    li_order = np.repeat(ord_keys, lines_per)
    li_odate = np.repeat(o_date, lines_per)
    li_lineno = np.concatenate([np.arange(1, c + 1) for c in lines_per])
    l_part = rng.integers(1, n_part + 1, n_li)
    l_supp = ((l_part + rng.integers(0, 4, n_li) * (n_supp // 4 + 1)) % n_supp) + 1
    qty = rng.integers(1, 51, n_li)
    retail = 900 + (l_part % 1000) / 10 + 100 * (l_part % 10)
    eprice = np.round(qty * retail, 2)
    ship = li_odate + rng.integers(1, 122, n_li)
    commit = li_odate + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    today = tpch._EPOCH_1992 + 1839  # 1995-06-17 per spec currentdate
    rflag = np.where(receipt <= today,
                     np.where(rng.random(n_li) < 0.5, "R", "A"), "N")
    lstatus = np.where(ship > today, "O", "F")
    out["lineitem"] = {
        "l_orderkey": li_order.tolist(),
        "l_partkey": l_part.tolist(),
        "l_suppkey": l_supp.tolist(),
        "l_linenumber": li_lineno.tolist(),
        "l_quantity": qty.astype(float).tolist(),
        "l_extendedprice": eprice.tolist(),
        "l_discount": np.round(rng.integers(0, 11, n_li) / 100, 2).tolist(),
        "l_tax": np.round(rng.integers(0, 9, n_li) / 100, 2).tolist(),
        "l_returnflag": rflag.tolist(),
        "l_linestatus": lstatus.tolist(),
        "l_shipdate": ship.tolist(),
        "l_commitdate": commit.tolist(),
        "l_receiptdate": receipt.tolist(),
        "l_shipinstruct": [tpch.SHIPINSTRUCT[i] for i in rng.integers(0, 4, n_li)],
        "l_shipmode": [tpch.SHIPMODES[i] for i in rng.integers(0, 7, n_li)],
        "l_comment": _list_comments(rng, n_li),
    }

    # orders.o_orderstatus consistency: F if all lines F, O if all O, else P
    import collections
    status_by_order: Dict[int, set] = collections.defaultdict(set)
    for k, s in zip(li_order.tolist(), lstatus.tolist()):
        status_by_order[k].add(s)
    o_status = []
    totals = collections.defaultdict(float)
    for k, p in zip(li_order.tolist(), eprice.tolist()):
        totals[k] += p
    for k in ord_keys.tolist():
        st = status_by_order.get(k)
        if not st:
            o_status.append("O")
        elif st == {"F"}:
            o_status.append("F")
        elif st == {"O"}:
            o_status.append("O")
        else:
            o_status.append("P")
    out["orders"]["o_orderstatus"] = o_status
    out["orders"]["o_totalprice"] = [round(totals.get(k, 0.0), 2)
                                     for k in ord_keys.tolist()]
    return out


SCALES = (0.01, 0.05)
SEEDS = (19920101, 3141592653)


@functools.lru_cache(maxsize=None)
def both(sf: float, seed: int):
    return list_generate(sf, seed), tpch.generate_arrays(sf, seed)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("sf", SCALES)
@pytest.mark.parametrize("table", tpch.TABLE_ORDER)
def test_generate_arrays_equals_the_list_generator_value_for_value(table, sf, seed):
    lists, arrays = both(sf, seed)
    assert list(arrays) == list(lists) == tpch.TABLE_ORDER
    assert list(arrays[table]) == list(lists[table])
    for name, want in lists[table].items():
        col = arrays[table][name]
        assert isinstance(col, (np.ndarray, EncodedStrings)), name
        assert len(col) == len(want), name
        got = col.tolist()
        assert got == want, name
        assert {type(v) for v in got} == {type(v) for v in want}, name
        assert np.asarray(col).tolist() == want, name


@pytest.mark.parametrize("seed", SEEDS)
def test_generate_hands_out_the_same_lists(seed):
    lists, _ = both(0.01, seed)
    got = tpch.generate(0.01, seed)
    assert got == lists
    assert all(type(col) is list for cols in got.values() for col in cols.values())


@pytest.mark.parametrize("seed", SEEDS)
def test_order_status_and_total_follow_the_lines_to_the_cent(seed):
    _, arrays = both(0.05, seed)
    li, o = arrays["lineitem"], arrays["orders"]
    cents = np.round(np.asarray(li["l_extendedprice"]) * 100).astype(np.int64)
    order, line_of = np.unique(li["l_orderkey"], return_inverse=True)
    assert order.tolist() == o["o_orderkey"].tolist()   # every order has a line
    total = np.zeros(len(order), np.int64)
    np.add.at(total, line_of, cents)
    assert np.round(o["o_totalprice"] * 100).astype(np.int64).tolist() == total.tolist()
    open_lines = np.zeros(len(order), np.int64)
    np.add.at(open_lines, line_of, np.asarray(li["l_linestatus"]) == "O")
    lines = np.bincount(line_of)
    want = np.where(open_lines == 0, "F", np.where(open_lines == lines, "O", "P"))
    assert np.asarray(o["o_orderstatus"]).tolist() == want.tolist()
    assert set(want.tolist()) == {"F", "O", "P"}


def test_a_column_drawn_in_steps_is_the_column_drawn_whole(monkeypatch):
    whole = tpch.generate_arrays(0.01, 7)
    monkeypatch.setattr(tpch, "_STEP", 1000)     # 60 steps a lineitem column
    stepped = tpch.generate_arrays(0.01, 7)
    for table, cols in whole.items():
        for name, col in cols.items():
            assert np.array_equal(np.asarray(col), np.asarray(stepped[table][name])), name


def test_row_counts_by_formula():
    assert tpch.row_counts(10) == {
        "region": 5, "nation": 25, "supplier": 100_000, "part": 2_000_000,
        "partsupp": 8_000_000, "customer": 1_500_000, "orders": 15_000_000,
        "lineitem": 60_000_000}
    _, arrays = both(0.05, SEEDS[0])
    counts = tpch.row_counts(0.05)
    for table, cols in arrays.items():
        n = len(next(iter(cols.values())))
        if table == "lineitem":     # drawn: 1-7 lines an order, 4 on average
            assert abs(n - counts[table]) < 0.01 * counts[table]
        else:
            assert n == counts[table]


def test_encoded_strings_answer_as_their_strings():
    col = EncodedStrings(np.array([2, 0, -1, 1, 2], np.int8), ["a", "b", "c"])
    assert len(col) == 5
    assert col.tolist() == ["c", "a", None, "b", "c"] == list(col)
    assert np.asarray(col).tolist()[:2] == ["c", "a"]
    assert np.asarray(col).dtype.kind == "U"
