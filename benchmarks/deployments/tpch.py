"""Deployment kind `tpch`: TPC-H tables as `tpch_ddl.sql` declares them, data
from the seed, loaded in bulk, and the plain reference that decides `correct`.

The reference (pandas/numpy over the generated columns, every DECIMAL in exact
scaled integers) and the comparisons are a copy of `chip_smoke.py`'s
`Reference` and `check_q1/q3/q5/q6`, kept here so that the program cannot move
the yardstick.  Nothing of the program (and no JAX) is imported before `load`
is called."""

from __future__ import annotations

import datetime
import os
import time
from decimal import Decimal

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def iso(day: int) -> str:
    return (datetime.date(1970, 1, 1) + datetime.timedelta(int(day))).isoformat()


def dec(unscaled: int, scale: int) -> Decimal:
    return Decimal(int(unscaled)).scaleb(-scale)


class Reference:
    """Plain answers to Q1/Q3/Q5/Q6 (validation parameters) over the columns
    the generator returned, independent of the engine's code."""

    def __init__(self, data):
        import pandas as pd
        li = data["lineitem"]
        self.li = pd.DataFrame({
            "ok": np.asarray(li["l_orderkey"], np.int64),
            "sk": np.asarray(li["l_suppkey"], np.int64),
            "qty": np.round(np.asarray(li["l_quantity"]) * 100).astype(np.int64),
            "price": np.round(np.asarray(li["l_extendedprice"]) * 100
                              ).astype(np.int64),
            "disc": np.round(np.asarray(li["l_discount"]) * 100).astype(np.int64),
            "tax": np.round(np.asarray(li["l_tax"]) * 100).astype(np.int64),
            "flag": np.asarray(li["l_returnflag"]),
            "status": np.asarray(li["l_linestatus"]),
            "ship": np.asarray(li["l_shipdate"], np.int64),
        })
        o = data["orders"]
        self.orders = pd.DataFrame({
            "ok": np.asarray(o["o_orderkey"], np.int64),
            "ck": np.asarray(o["o_custkey"], np.int64),
            "od": np.asarray(o["o_orderdate"], np.int64),
            "sp": np.asarray(o["o_shippriority"], np.int64),
        })
        c = data["customer"]
        self.cust = pd.DataFrame({
            "ck": np.asarray(c["c_custkey"], np.int64),
            "cnk": np.asarray(c["c_nationkey"], np.int64),
            "seg": np.asarray(c["c_mktsegment"]),
        })
        s = data["supplier"]
        self.supp = pd.DataFrame({
            "sk": np.asarray(s["s_suppkey"], np.int64),
            "snk": np.asarray(s["s_nationkey"], np.int64),
        })
        n = data["nation"]
        self.nation = pd.DataFrame({
            "nk": np.asarray(n["n_nationkey"], np.int64),
            "name": np.asarray(n["n_name"]),
            "rk": np.asarray(n["n_regionkey"], np.int64),
        })
        r = data["region"]
        self.region = pd.DataFrame({
            "rk": np.asarray(r["r_regionkey"], np.int64),
            "rname": np.asarray(r["r_name"]),
        })

    def q1(self):
        f = self.li[self.li.ship <= days(1998, 12, 1) - 90]
        dp = f.price * (100 - f.disc)                      # scale 4
        g = f.assign(dp=dp, ch=dp * (100 + f.tax)).groupby(  # ch: scale 6
            ["flag", "status"], sort=True).agg(
            sq=("qty", "sum"), sp=("price", "sum"), sdp=("dp", "sum"),
            sch=("ch", "sum"), sd=("disc", "sum"), n=("qty", "size"))
        out = []
        for (flag, status), r in g.iterrows():
            n = int(r.n)
            out.append((flag, status, dec(r.sq, 2), dec(r.sp, 2), dec(r.sdp, 4),
                        dec(r.sch, 6), dec(r.sq, 2) / n, dec(r.sp, 2) / n,
                        dec(r.sd, 2) / n, n))
        return out

    def q6(self):
        f = self.li[(self.li.ship >= days(1994, 1, 1)) &
                    (self.li.ship < days(1995, 1, 1)) &
                    (self.li.disc >= 5) & (self.li.disc <= 7) &
                    (self.li.qty < 2400)]
        return [(dec((f.price * f.disc).sum(), 4),)]

    def q3(self):
        """Every Q3 group as {(orderkey, date, prio): revenue}: the served
        top-10 is checked against this, robust to ties at the LIMIT edge."""
        cutoff = days(1995, 3, 15)
        c = self.cust[self.cust.seg == "BUILDING"]
        o = self.orders[self.orders.od < cutoff].merge(c, on="ck")
        li = self.li[self.li.ship > cutoff]
        j = li.merge(o, on="ok")
        rev = (j.price * (100 - j.disc)).groupby(
            [j.ok, j.od, j.sp], sort=False).sum()
        return {(int(k[0]), int(k[1]), int(k[2])): int(v)
                for k, v in rev.items()}

    def q5(self):
        asia = self.nation.merge(self.region[self.region.rname == "ASIA"],
                                 on="rk")
        o = self.orders[(self.orders.od >= days(1994, 1, 1)) &
                        (self.orders.od < days(1995, 1, 1))]
        j = self.li.merge(o, on="ok").merge(self.cust, on="ck") \
            .merge(self.supp, on="sk")
        j = j[j.cnk == j.snk].merge(asia, left_on="snk", right_on="nk")
        rev = (j.price * (100 - j.disc)).groupby(j.name).sum()
        rows = sorted(((name, int(v)) for name, v in rev.items()),
                      key=lambda r: -r[1])
        return [(name, dec(v, 4)) for name, v in rows]


# The engine sums DECIMALs exactly in scaled int64 but renders a result through
# float64 on the wire, so a value past 2^53 unscaled units (Q1's scale-6
# sum_charge at SF1) arrives rounded to the nearest double.  Sums are held to
# that rendering error and nothing more; averages additionally to the half unit
# of the engine's DECIMAL(...,6) average.
F64 = Decimal(2) ** -52


def same_decimal(got: str, want: Decimal, half_unit=Decimal(0)) -> bool:
    return abs(Decimal(got) - want) <= abs(want) * F64 + half_unit


def check_q1(rows, ref):
    assert len(rows) == len(ref), f"Q1: {len(rows)} groups, reference {len(ref)}"
    for got, want in zip(rows, ref):
        assert (got[0], got[1]) == (want[0], want[1]), (got, want)
        for i in (2, 3, 4, 5):
            assert same_decimal(got[i], want[i]), f"Q1 col {i}: {got} != {want}"
        for i in (6, 7, 8):
            assert same_decimal(got[i], want[i], Decimal("0.0000005")), \
                f"Q1 avg col {i}: {got} != {want}"
        assert int(got[9]) == want[9], (got, want)


def check_q6(rows, ref):
    assert len(rows) == 1 and same_decimal(rows[0][0], ref[0][0]), (rows, ref)


def check_q3(rows, groups):
    got = [(int(r[0]), Decimal(r[1]), r[2], int(r[3])) for r in rows]
    want_n = min(10, len(groups))
    assert len(got) == want_n, f"Q3: {len(got)} rows, reference {want_n}"
    for ok, rev, od, sp in got:
        key = (ok, days(*map(int, od.split("-"))), sp)
        assert key in groups, f"Q3: group {key} not in the reference"
        assert same_decimal(rev, dec(groups[key], 4)), (key, rev, groups[key])
    keys = [(-rev, od) for _, rev, od, _ in got]
    assert keys == sorted(keys), f"Q3: not ordered by revenue desc, date: {got}"
    top = sorted((-v, iso(k[1])) for k, v in groups.items())[:want_n]
    assert [(k[0], k[1]) for k in keys] == \
        [(dec(v, 4), d) for v, d in top], "Q3: not the reference's top rows"


def check_q5(rows, ref):
    assert len(rows) == len(ref) and all(
        g[0] == w[0] and same_decimal(g[1], w[1])
        for g, w in zip(rows, ref)), f"Q5: {rows} != {ref}"


CHECKS = {"q1": check_q1, "q3": check_q3, "q5": check_q5, "q6": check_q6}


def read_ddl() -> dict:
    """{table: CREATE TABLE ...} in file order (the load order)."""
    with open(os.path.join(HERE, "tpch_ddl.sql")) as f:
        stmts = [s.strip() for s in f.read().split(";") if s.strip()]
    return {s.split()[2]: s for s in stmts}


class Deployment:
    def __init__(self, served, config, reference, rows, timings):
        self.served = served
        self.config = config
        self.database = config["database"]
        self.rows = rows
        self.timings = timings
        self._reference = reference
        self._expected = {}

    def prepare(self, checks):
        """Work out the plain answers the cell needs, then let the frames go."""
        for name in checks:
            self._expected[name] = getattr(self._reference, name)()
        self._reference = None

    def check(self, name: str, rows):
        """Raises AssertionError unless `rows` is the reference's answer."""
        CHECKS[name](rows, self._expected[name])

    def lane_bytes(self, reads: dict) -> int:
        """Bytes of the column lanes a statement's plan reads, each once: data
        lane plus its one-byte validity lane, summed over partitions."""
        total = 0
        for table, cols in reads.items():
            store = self.served.instance.store(self.database, table)
            for part in store.partitions:
                for c in cols:
                    total += part.lanes[c][:part.num_rows].nbytes
                    total += part.valid[c][:part.num_rows].nbytes
        return total

    def engine_counts(self) -> dict:
        c = self.served.instance.counters
        return {"mpp_queries": int(c["mpp_queries"]),
                "mpp_fallback_local": int(c["mpp_fallback_local"])}

    def check_engine(self, before: dict, after: dict, statements: int):
        """The configuration names its engine; a run that took another one
        measured something else."""
        ran = after["mpp_queries"] - before["mpp_queries"]
        fell = after["mpp_fallback_local"] - before["mpp_fallback_local"]
        if self.config["engine"] == "mpp":
            assert ran == statements and fell == 0, \
                f"MPP ran {ran} of {statements} statements, {fell} fell back"
        else:
            assert ran == 0, f"{ran} statements took the MPP engine"


def load(served, config, seed: int, dry_run: bool) -> Deployment:
    from galaxysql_tpu.storage import tpch as generator
    sf = config["dry_run_scale_factor"] if dry_run else config["scale_factor"]
    t0 = time.perf_counter()
    data = generator.generate(sf, seed=seed)
    t1 = time.perf_counter()
    inst = served.instance
    if dry_run and config["engine"] == "mpp":
        # rehearsal only: the tiny scale scans fewer rows than the threshold
        # every real-scale query here clears by itself
        inst.config.set_instance("MPP_MIN_AP_ROWS", 1)
    c = served.connect()
    try:
        c.query(f"CREATE DATABASE {config['database']}")
        c.query(f"USE {config['database']}")
        ddl = read_ddl()
        for table, stmt in ddl.items():
            c.query(stmt)
            inst.store(config["database"], table).insert_arrays(
                data[table], inst.tso.next_timestamp())
        c.query("ANALYZE TABLE " + ", ".join(ddl))
        rows = {}
        for table in ddl:
            want = len(next(iter(data[table].values())))
            got = int(c.query(f"SELECT COUNT(*) FROM {table}")[1][0][0])
            assert got == want, f"{table}: COUNT(*) = {got}, generated {want}"
            rows[table] = got
    finally:
        c.close()
    t2 = time.perf_counter()
    return Deployment(served, config, Reference(data), rows,
                      {"generate_s": t1 - t0, "load_s": t2 - t1})
