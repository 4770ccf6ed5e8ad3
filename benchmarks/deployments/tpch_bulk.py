"""Deployment kind `tpch_bulk`: kind `tpch`'s tables, comparisons, `Deployment`
and engine check (`deployments/tpch.py`, loaded as a module of this one's own)
at a scale where a column is an array or it is nothing: the data comes from the
program's `generate_arrays(sf, seed)` (numpy arrays, small-domain strings as
codes and their dictionary) and goes into the store column by column, each
table's columns let go as soon as they are loaded.

On a program without `generate_arrays` the run ends at once, before any data is
made: the list generator at SF10 would build a billion Python objects.

The reference is numpy over the generated columns and written from each
query's meaning, `lineitem` taken in blocks: Q3 asks, of every order of a
BUILDING customer placed before the date, what its lines shipped after the
date bring; Q5 asks, of every line of an order of 1994 whose supplier's nation
is the customer's and lies in ASIA, what it brings to that nation.  Revenue is
`price * (100 - discount)` in exact scaled integers.  Kind `tpch`'s pandas
reference holds all of `lineitem` in one frame and merges it twice (at SF10:
`benchmarks/README_scale.md` has its seconds and memory); the answers of the
two are the same objects (`benchmarks/tests/test_bulk.py`).  Nothing of the
program (and no JAX) is imported before `load` is called."""

from __future__ import annotations

import os
import resource
import time

import numpy as np

from benchmarks.harness.byname import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
tpch = load_module(os.path.join(HERE, "tpch.py"))
days, dec = tpch.days, tpch.dec
CHECKS = tpch.CHECKS
Deployment = tpch.Deployment

BLOCK = 1 << 23  # rows of `lineitem` a step


def cents(column, lo: int, hi: int):
    """Rows [lo, hi) of a DECIMAL(15,2) column as exact scaled integers."""
    return np.round(np.asarray(column[lo:hi], np.float64) * 100).astype(np.int64)


def positions(sorted_keys, keys):
    """(position of each of `keys` in `sorted_keys`, whether it is there)."""
    pos = np.minimum(np.searchsorted(sorted_keys, keys), len(sorted_keys) - 1)
    return pos, sorted_keys[pos] == keys


class Reference:
    """Plain answers to Q3 and Q5 (validation parameters) over the columns the
    generator returned, independent of the engine's code."""

    def __init__(self, data):
        li, o, c, s, n, r = (data[t] for t in (
            "lineitem", "orders", "customer", "supplier", "nation", "region"))
        self.l_ok = np.asarray(li["l_orderkey"], np.int64)
        self.l_sk = np.asarray(li["l_suppkey"], np.int64)
        self.l_ship = np.asarray(li["l_shipdate"], np.int64)
        self.l_price, self.l_disc = li["l_extendedprice"], li["l_discount"]
        self.o_ok = np.asarray(o["o_orderkey"], np.int64)
        self.o_ck = np.asarray(o["o_custkey"], np.int64)
        self.o_od = np.asarray(o["o_orderdate"], np.int64)
        self.o_sp = np.asarray(o["o_shippriority"], np.int64)
        self.c_ck = np.asarray(c["c_custkey"], np.int64)
        self.c_nk = np.asarray(c["c_nationkey"], np.int64)
        self.c_seg = np.asarray(c["c_mktsegment"])
        self.s_sk = np.asarray(s["s_suppkey"], np.int64)
        self.s_nk = np.asarray(s["s_nationkey"], np.int64)
        self.n_nk = np.asarray(n["n_nationkey"], np.int64)
        self.n_name = np.asarray(n["n_name"])
        self.n_rk = np.asarray(n["n_regionkey"], np.int64)
        self.r_rk = np.asarray(r["r_regionkey"], np.int64)
        self.r_name = np.asarray(r["r_name"])

    def _orders(self, wanted):
        """The wanted orders' (keys ascending, their rows in `orders`)."""
        rows = np.flatnonzero(wanted)
        rows = rows[np.argsort(self.o_ok[rows], kind="stable")]
        return self.o_ok[rows], rows

    def _revenue(self, lo: int, hi: int):
        return cents(self.l_price, lo, hi) * (100 - cents(self.l_disc, lo, hi))

    def q3(self):
        """Every Q3 group as {(orderkey, date, prio): revenue}, as kind
        `tpch`'s `q3` gives them."""
        cutoff = days(1995, 3, 15)
        building = np.zeros(int(self.c_ck.max()) + 1, bool)
        building[self.c_ck[self.c_seg == "BUILDING"]] = True
        keys, rows = self._orders((self.o_od < cutoff) & building[self.o_ck])
        revenue = np.zeros(len(keys), np.int64)
        lines = np.zeros(len(keys), np.int64)
        for lo in range(0, len(self.l_ok), BLOCK):
            hi = min(lo + BLOCK, len(self.l_ok))
            pos, found = positions(keys, self.l_ok[lo:hi])
            found &= self.l_ship[lo:hi] > cutoff
            np.add.at(revenue, pos[found], self._revenue(lo, hi)[found])
            np.add.at(lines, pos[found], 1)
        kept = np.flatnonzero(lines)
        return dict(zip(zip(keys[kept].tolist(), self.o_od[rows[kept]].tolist(),
                            self.o_sp[rows[kept]].tolist()),
                        revenue[kept].tolist()))

    def q5(self):
        """[(nation, revenue)] ordered by revenue descending."""
        asia = np.zeros(int(self.n_nk.max()) + 1, bool)
        asia[self.n_nk[np.isin(self.n_rk, self.r_rk[self.r_name == "ASIA"])]] = True
        of_customer = np.zeros(int(self.c_ck.max()) + 1, np.int64)
        of_customer[self.c_ck] = self.c_nk
        of_supplier = np.zeros(int(self.s_sk.max()) + 1, np.int64)
        of_supplier[self.s_sk] = self.s_nk
        keys, rows = self._orders((self.o_od >= days(1994, 1, 1)) &
                                  (self.o_od < days(1995, 1, 1)))
        order_nation = of_customer[self.o_ck[rows]]
        revenue = np.zeros(len(asia), np.int64)
        lines = np.zeros(len(asia), np.int64)
        for lo in range(0, len(self.l_ok), BLOCK):
            hi = min(lo + BLOCK, len(self.l_ok))
            pos, found = positions(keys, self.l_ok[lo:hi])
            nation = of_supplier[self.l_sk[lo:hi]]
            found &= (nation == order_nation[pos]) & asia[nation]
            np.add.at(revenue, nation[found], self._revenue(lo, hi)[found])
            np.add.at(lines, nation[found], 1)
        name_of = dict(zip(self.n_nk.tolist(), self.n_name.tolist()))
        out = sorted(((name_of[k], int(revenue[k])) for k in np.flatnonzero(lines)),
                     key=lambda row: -row[1])
        return [(name, dec(v, 4)) for name, v in out]


# what `Reference` reads: the rest of a table is let go once it is loaded
REFERENCE_READS = {
    "lineitem": ("l_orderkey", "l_suppkey", "l_shipdate", "l_extendedprice",
                 "l_discount"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "customer": ("c_custkey", "c_nationkey", "c_mktsegment"),
    "supplier": ("s_suppkey", "s_nationkey"),
    "nation": ("n_nationkey", "n_name", "n_regionkey"),
    "region": ("r_regionkey", "r_name"),
}


def load(served, config, seed: int, dry_run: bool) -> Deployment:
    from galaxysql_tpu.storage import tpch as generator
    if not hasattr(generator, "generate_arrays"):
        raise SystemExit(
            "kind tpch_bulk: galaxysql_tpu/storage/tpch.py has no "
            "generate_arrays(sf, seed); this program makes its data as Python "
            "lists, which this scale cannot be loaded from.  Nothing was made.")
    sf = config["dry_run_scale_factor"] if dry_run else config["scale_factor"]
    t0 = time.perf_counter()
    data = generator.generate_arrays(sf, seed=seed)
    t1 = time.perf_counter()
    reference = Reference({t: {c: data[t][c] for c in cols}
                           for t, cols in REFERENCE_READS.items()})
    inst = served.instance
    c = served.connect()
    try:
        c.query(f"CREATE DATABASE {config['database']}")
        c.query(f"USE {config['database']}")
        ddl = tpch.read_ddl()
        generated = {}
        for table, stmt in ddl.items():
            c.query(stmt)
            columns = data.pop(table)
            generated[table] = len(next(iter(columns.values())))
            inst.store(config["database"], table).insert_arrays(
                columns, inst.tso.next_timestamp())
            del columns
        t_loaded = time.perf_counter()
        c.query("ANALYZE TABLE " + ", ".join(ddl))
        t_analyzed = time.perf_counter()
        rows = {}
        for table, want in generated.items():
            got = int(c.query(f"SELECT COUNT(*) FROM {table}")[1][0][0])
            assert got == want, f"{table}: COUNT(*) = {got}, generated {want}"
            rows[table] = got
    finally:
        c.close()
    t2 = time.perf_counter()
    return Deployment(served, config, reference, rows, {
        "generate_s": t1 - t0, "load_s": t2 - t1,
        "insert_s": t_loaded - t1, "analyze_s": t_analyzed - t_loaded,
        "host_peak_rss_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024})
