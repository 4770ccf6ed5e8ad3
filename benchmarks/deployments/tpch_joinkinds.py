"""Deployment kind `tpch_joinkinds`: the tables, data and load of kind `tpch`
(`deployments/tpch.py`, used as it is) with the plain reference of the three
TPC-H queries whose joins are not inner and that one chip runs: Q13 (a LEFT
OUTER JOIN with a NOT LIKE in its ON clause), Q22 (NOT EXISTS behind a scalar
subquery) and Q4 (EXISTS over `lineitem`, taken from `deployments/tpch_subq.py`
as it is), at their validation parameters.

The reference is numpy over the generated columns and written from each
query's meaning, not as the engine's plan (no join, no pair is enumerated):
Q13 asks how many customers have 0, 1, 2, ... orders whose comment does not
read `special ... requests`; Q22 asks, by country code, for the customers of
seven codes whose balance is over the average positive balance of those codes
and who have no order.  Counts are exact integers and sums exact cents.
Nothing of the program (and no JAX) is imported before `load` is called.

`Deployment` also takes the program's local-join counters where the driver
takes the engine's (at the window's two ends), so that the two counter
metrics read a window and not warm-up's first climb."""

from __future__ import annotations

import os
import re

import numpy as np

from benchmarks.harness import local_joins
from benchmarks.harness.byname import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
# copies of kind `tpch` and kind `tpch_subq` that are this module's own
# (`load_module` registers nothing): `load` runs as written in the first, over
# the reference, the comparisons and the `Deployment` below; Q4's reference and
# comparison are the second's
tpch = load_module(os.path.join(HERE, "tpch.py"))
subq = load_module(os.path.join(HERE, "tpch_subq.py"))

Q13_WORDS = re.compile(r"special.*requests")     # WORD1, WORD2
Q22_CODES = ("13", "31", "23", "29", "30", "18", "17")


def cents(values) -> np.ndarray:
    return np.round(np.asarray(values, np.float64) * 100).astype(np.int64)


class Reference:
    """Plain answers to Q13, Q22 and Q4 over the columns the generator
    returned, independent of the engine's code."""

    def __init__(self, data):
        c, o = data["customer"], data["orders"]
        self.c_ck = np.asarray(c["c_custkey"], np.int64)
        self.c_phone = np.asarray(c["c_phone"])
        self.c_cents = cents(c["c_acctbal"])
        self.o_ck = np.asarray(o["o_custkey"], np.int64)
        self.o_comment = np.asarray(o["o_comment"])
        self._subq = subq.Reference(data)

    def q4(self):
        return self._subq.q4()

    def q13(self):
        """[(c_count, custdist)] ordered custdist desc, c_count desc."""
        texts, code = np.unique(self.o_comment, return_inverse=True)
        matches = np.array([Q13_WORDS.search(str(t)) is not None
                            for t in texts])
        kept = self.o_ck[~matches[code]]
        # every customer has a row: one without such an order counts 0
        per_customer = np.bincount(kept, minlength=int(self.c_ck.max()) + 1
                                   )[self.c_ck]
        custdist = np.bincount(per_customer)
        rows = [(int(n), int(k)) for n, k in enumerate(custdist) if k]
        return sorted(rows, key=lambda r: (-r[1], -r[0]))

    def q22(self):
        """[(cntrycode, numcust, totacctbal in cents)] ordered by code."""
        code = np.array([str(p)[:2] for p in self.c_phone])
        of_codes = np.isin(code, Q22_CODES)
        positive = of_codes & (self.c_cents > 0)
        n, total = int(positive.sum()), int(self.c_cents[positive].sum())
        # c_acctbal > total / n in exact integers; the engine's average is a
        # DECIMAL(.., 6), so the two comparisons agree unless a candidate's
        # balance lies within 0.000001 of the average (0.0001 cents)
        margin = self.c_cents[of_codes] * n - total
        assert n and (np.abs(margin) * 10_000 > n).all(), \
            "Q22: a balance lies within 0.000001 of the average for this " \
            "seed: the engine's DECIMAL(.., 6) average may compare otherwise"
        rich = of_codes & (self.c_cents * n > total)
        dormant = rich & ~np.isin(self.c_ck, self.o_ck)
        rows = []
        for cc in sorted(set(code[dormant].tolist())):
            m = dormant & (code == cc)
            rows.append((cc, int(m.sum()), int(self.c_cents[m].sum())))
        return rows


def check_q13(rows, ref):
    got = [(int(r[0]), int(r[1])) for r in rows]
    assert len(got) == len(ref), f"Q13: {len(got)} rows, reference {len(ref)}"
    for i, (g, w) in enumerate(zip(got, ref)):
        assert g == w, f"Q13 row {i}: {g}, reference {w}"


def check_q22(rows, ref):
    assert len(rows) == len(ref), f"Q22: {len(rows)} rows, reference {len(ref)}"
    for got, want in zip(rows, ref):
        assert (got[0], int(got[1])) == want[:2], f"Q22: {got}, reference {want}"
        # the wire renders a DECIMAL through float64, as `tpch.py` holds Q1's
        assert tpch.same_decimal(got[2], tpch.dec(want[2], 2)), \
            f"Q22 sum: {got}, reference {want}"


class Deployment(tpch.Deployment):
    """Kind `tpch`'s, with the local joins' counters taken beside the
    engine's: `window_joins` is (window start, window end) once the driver has
    checked the engine, each `None` on a commit that keeps no such counter."""

    window_joins = (None, None)

    def engine_counts(self) -> dict:
        return dict(super().engine_counts(), joins=local_joins.join_stats())

    def check_engine(self, before: dict, after: dict, statements: int):
        self.window_joins = (before["joins"], after["joins"])
        super().check_engine(before, after, statements)


tpch.Reference = Reference
tpch.CHECKS = {"q4": subq.check_q4, "q13": check_q13, "q22": check_q22}
tpch.Deployment = Deployment
load = tpch.load
