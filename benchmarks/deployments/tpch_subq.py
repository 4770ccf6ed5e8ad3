"""Deployment kind `tpch_subq`: the tables, data and load of kind `tpch`
(`deployments/tpch.py`, used as it is) with the plain reference of TPC-H's
EXISTS / NOT EXISTS queries, Q4 and Q21 at their validation parameters.

The reference is numpy over the generated columns and written from each
query's meaning, not as the engine's plan (no join, no pair is enumerated):
Q4 asks which orders of a quarter have a late line; Q21 asks, of every late
line of a finished order, whether the order has another supplier and whether
this line's supplier is the only late one, which two counts an order answer
(its distinct suppliers, its distinct late suppliers).  All counts are exact
integers.  Nothing of the program (and no JAX) is imported before `load` is
called."""

from __future__ import annotations

import os

import numpy as np

from benchmarks.harness.byname import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
# a copy of kind `tpch` that is this module's own (`load_module` registers
# nothing): `load` and `Deployment` run as written there, over the reference
# and the comparisons below
tpch = load_module(os.path.join(HERE, "tpch.py"))
days = tpch.days


def distinct_per_order(orderkey, suppkey, n_supp: int):
    """(orderkeys, count of distinct suppkeys in each)."""
    pairs = np.unique(orderkey * np.int64(n_supp + 1) + suppkey)
    return np.unique(pairs // np.int64(n_supp + 1), return_counts=True)


def count_of(keys, counts, wanted):
    """`counts` of each of `wanted` in the sorted `keys`, 0 where absent."""
    pos = np.minimum(np.searchsorted(keys, wanted), len(keys) - 1)
    return np.where(keys[pos] == wanted, counts[pos], 0)


class Reference:
    """Plain answers to Q4 and Q21 over the columns the generator returned,
    independent of the engine's code."""

    def __init__(self, data):
        li, o, s, n = (data[t] for t in ("lineitem", "orders", "supplier",
                                         "nation"))
        self.l_ok = np.asarray(li["l_orderkey"], np.int64)
        self.l_sk = np.asarray(li["l_suppkey"], np.int64)
        self.l_late = (np.asarray(li["l_receiptdate"], np.int64) >
                       np.asarray(li["l_commitdate"], np.int64))
        self.o_ok = np.asarray(o["o_orderkey"], np.int64)
        self.o_date = np.asarray(o["o_orderdate"], np.int64)
        self.o_status = np.asarray(o["o_orderstatus"])
        self.o_prio = np.asarray(o["o_orderpriority"])
        self.s_sk = np.asarray(s["s_suppkey"], np.int64)
        self.s_name = np.asarray(s["s_name"])
        self.s_nk = np.asarray(s["s_nationkey"], np.int64)
        self.n_nk = np.asarray(n["n_nationkey"], np.int64)
        self.n_name = np.asarray(n["n_name"])

    def q4(self):
        """[(o_orderpriority, order_count)] ordered by priority."""
        late_orders = np.unique(self.l_ok[self.l_late])
        quarter = (self.o_date >= days(1993, 7, 1)) & \
                  (self.o_date < days(1993, 10, 1))
        hit = quarter & np.isin(self.o_ok, late_orders)
        prio, n = np.unique(self.o_prio[hit], return_counts=True)
        return [(str(p), int(c)) for p, c in zip(prio, n)]

    def q21(self):
        """[(s_name, numwait)] ordered numwait desc, s_name: the first 100."""
        n_supp = int(self.s_sk.max())
        ok, sk = self.l_ok[self.l_late], self.l_sk[self.l_late]  # late lines
        orders, suppliers = distinct_per_order(self.l_ok, self.l_sk, n_supp)
        late, late_suppliers = distinct_per_order(ok, sk, n_supp)
        finished = self.o_ok[self.o_status == "F"]
        saudi = self.n_nk[self.n_name == "SAUDI ARABIA"]
        of_saudi = np.zeros(n_supp + 1, bool)
        of_saudi[self.s_sk[np.isin(self.s_nk, saudi)]] = True
        waiting = (of_saudi[sk]
                   & np.isin(ok, finished)
                   & (count_of(orders, suppliers, ok) >= 2)
                   & (count_of(late, late_suppliers, ok) == 1))
        numwait = np.bincount(sk[waiting], minlength=n_supp + 1)
        name_of = dict(zip(self.s_sk.tolist(), self.s_name.tolist()))
        rows = sorted(((name_of[k], int(numwait[k]))
                       for k in np.flatnonzero(numwait)),
                      key=lambda r: (-r[1], r[0]))
        return rows[:100]


def check_q4(rows, ref):
    got = [(r[0], int(r[1])) for r in rows]
    assert got == ref, f"Q4: {got} != {ref}"


def check_q21(rows, ref):
    got = [(r[0], int(r[1])) for r in rows]
    assert len(got) == len(ref), f"Q21: {len(got)} rows, reference {len(ref)}"
    for i, (g, w) in enumerate(zip(got, ref)):
        assert g == w, f"Q21 row {i}: {g}, reference {w}"


tpch.Reference = Reference
tpch.CHECKS = {"q4": check_q4, "q21": check_q21}
Deployment = tpch.Deployment
load = tpch.load
