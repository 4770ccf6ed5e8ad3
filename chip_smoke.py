#!/usr/bin/env python3
"""chip_smoke.py — the served SQL path, end to end, on the chip.

One process (the chip belongs to one process; nothing here spawns a child
that imports JAX) builds what `python -m galaxysql_tpu.net.server` builds —
an `Instance` behind a `MySQLServer` on port 0 — and talks to it only through
a socket with `MiniClient`:

- loads TPC-H at `--sf` (default 1, the smallest scale the specification
  defines) generated from `--seed`, in bulk through `TableStore.insert_arrays`
  + `ANALYZE TABLE`, as `__graft_entry__` does;
- AP leg: Q1, Q6, Q3, Q5 over the wire, each once cold and once more under
  `FRAGMENT_CACHE(OFF)` (a warm fragment-cache hit executes nothing).  Every
  result is compared in full with a plain pandas/numpy reference over the
  same generated data, outside any timing;
- TP leg: a primary-key table, a few thousand INSERTed rows, point SELECTs,
  an UPDATE read back by the same and by a second connection,
  BEGIN/INSERT/ROLLBACK leaving no row.  Acknowledged writes read back exactly.

It refuses to run unless `jax.devices()[0].platform == "tpu"`; `--dry-run-cpu`
is the explicit debugging mode (tiny `--sf`), marks its output as a dry run and
is never chosen by the program.  Any failed phase raises: the exit code is
non-zero and no result line is printed.  The full report is the `"phase":
"report"` line (also written to `chiprun_out/`); wall seconds in it are
labelled one-off observations, not metrics.  The last line of stdout is one
JSON object with exactly these keys, the device as JAX reports it:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import asyncio
import datetime
import json
import os
import sys
import threading
import time
from decimal import Decimal

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

AP_QUERIES = (1, 6, 3, 5)
NO_FRAG = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def iso(day: int) -> str:
    return (datetime.date(1970, 1, 1) + datetime.timedelta(int(day))).isoformat()


def dec(unscaled: int, scale: int) -> Decimal:
    return Decimal(int(unscaled)).scaleb(-scale)


# ---------------------------------------------------------------------------
# plain reference: pandas/numpy over the generated columns, exact integers for
# every DECIMAL (cents, hundredths), independent of the engine's code
# ---------------------------------------------------------------------------

class Reference:
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

    def q3_groups(self):
        """Every Q3 group as {(orderkey, date, prio): revenue} — the served
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


# The engine sums DECIMALs exactly in scaled int64 but renders a result
# through float64 on the wire, so a value past 2^53 unscaled units (Q1's
# scale-6 sum_charge at SF1) arrives rounded to the nearest double.  Sums are
# held to that rendering error and nothing more; averages additionally to the
# half unit of the engine's DECIMAL(…,6) average.
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


# ---------------------------------------------------------------------------
# the server, as net/server.py:main builds it, on a thread loop
# ---------------------------------------------------------------------------

class ServedInstance:
    def __init__(self):
        from galaxysql_tpu.net.server import MySQLServer
        from galaxysql_tpu.server.instance import Instance
        # memory-only: the engine's AOT cache (<data_dir>/compile_cache) stays
        # detached; JAX's persistent cache is the one under observation
        self.instance = Instance()
        self.server = MySQLServer(self.instance, "127.0.0.1", 0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True,
                                       name="mysql-server")
        self.thread.start()
        if not started.wait(30):
            raise RuntimeError("MySQLServer did not start listening in 30 s")

    def connect(self, database=None):
        from galaxysql_tpu.net.client import MiniClient
        return MiniClient("127.0.0.1", self.server.port, database=database,
                          timeout=1100.0)

    def stop(self):
        asyncio.run_coroutine_threadsafe(self.server.stop(),
                                         self.loop).result(30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(30)
        if self.thread.is_alive():
            raise RuntimeError("server thread did not stop")


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def lane_devices(arrays) -> list:
    return sorted({str(d) for a in arrays for d in a.sharding.device_set})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=19920101,
                    help="TPC-H generator seed")
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (1 = the contract's size)")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="debugging only: force the CPU platform (use a tiny "
                         "--sf); the output is marked as a dry run")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax
    from galaxysql_tpu import native, runtime  # package import enables x64
    if args.dry_run_cpu:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = runtime.enable_compile_cache()
    cache_before = cache_entries(cache_dir)
    dev = runtime.device_report()  # a backend that cannot start raises here
    if dev["platform"] != "tpu" and not args.dry_run_cpu:
        sys.exit(f"chip_smoke: JAX found no TPU (default backend "
                 f"{dev['platform']!r}, {dev['device_kind']}); refusing to "
                 f"run.  --dry-run-cpu is the explicit debugging mode.")
    tag = dict(dev, dry_run=args.dry_run_cpu)

    def say(phase, **kv):
        print(json.dumps({"phase": phase, **kv, **tag}), flush=True)

    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.exec.programs import PROGRAMS
    from galaxysql_tpu.exec.device_cache import (GLOBAL_DEVICE_CACHE,
                                                 hbm_high_water)
    from galaxysql_tpu.parallel.mesh import GLOBAL_MESH_CACHE
    from galaxysql_tpu.storage import tpch
    from galaxysql_tpu.storage.tpch_queries import QUERIES

    # -- load ----------------------------------------------------------------
    t0 = time.perf_counter()
    data = tpch.generate(args.sf, seed=args.seed)
    gen_s = time.perf_counter() - t0
    served = ServedInstance()
    inst = served.instance
    c = served.connect()
    c.query("CREATE DATABASE tpch")
    c.query("USE tpch")
    t0 = time.perf_counter()
    for t in tpch.TABLE_ORDER:
        c.query(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t],
                                            inst.tso.next_timestamp())
    c.query("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    load_s = time.perf_counter() - t0
    rows_loaded = {}
    for t in tpch.TABLE_ORDER:
        want = len(next(iter(data[t].values())))
        got = int(c.query(f"SELECT COUNT(*) FROM {t}")[1][0][0])
        assert got == want, f"{t}: COUNT(*) = {got}, generated {want}"
        rows_loaded[t] = got
    say("load", sf=args.sf, seed=args.seed, rows=rows_loaded,
        generate_wall_s=round(gen_s, 1), load_wall_s=round(load_s, 1))

    ref = Reference(data)
    reference = {1: ref.q1(), 6: ref.q6(), 3: ref.q3_groups(), 5: ref.q5()}
    check = {1: check_q1, 6: check_q6, 3: check_q3, 5: check_q5}
    del data

    # -- a TP-pinned statement touches lineitem BEFORE any AP scan: its lanes
    #    land on the CPU device and must not be what the AP leg reads ---------
    pinned = int(c.query("/*+TDDL:ENGINE(TP)*/ SELECT COUNT(*) FROM lineitem "
                         "WHERE l_quantity < 24")[1][0][0])
    assert pinned == int((ref.li.qty < 2400).sum()), pinned
    pinned_trace = [r[0] for r in c.query("SHOW TRACE")[1]]
    pinned_dev = [ln.split(" ", 1)[1] for ln in pinned_trace
                  if ln.startswith("exec-device ")]
    assert pinned_dev and all("cpu" in d.lower() for d in pinned_dev), \
        f"ENGINE(TP) statement did not run on the CPU device: {pinned_trace}"
    pinned_keys = set(GLOBAL_DEVICE_CACHE._map)
    pinned_cols = {k[2] for k in pinned_keys if k[0] ==
                   inst.store("tpch", "lineitem").uid}
    assert pinned_cols, "the ENGINE(TP) statement cached no lineitem lane"

    # -- AP leg --------------------------------------------------------------
    queries = {}
    for qid in AP_QUERIES:
        q = QUERIES[qid]
        mpp0 = inst.counters["mpp_queries"]
        t0 = time.perf_counter()
        _, cold = c.query(q)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, warm = c.query(NO_FRAG + q)
        warm_s = time.perf_counter() - t0
        engine = "mpp" if inst.counters["mpp_queries"] - mpp0 == 2 else \
            "local" if inst.counters["mpp_queries"] == mpp0 else "mixed"
        check[qid](cold, reference[qid])
        check[qid](warm, reference[qid])
        entry = {"correct": True, "engine": engine, "rows": len(cold),
                 "cold_wall_s_observed": round(cold_s, 3),
                 "warm_wall_s_observed": round(warm_s, 3)}
        queries[f"q{qid}"] = entry
        say(f"q{qid}", **entry)

    ap_local = [v for k, v in GLOBAL_DEVICE_CACHE._map.items()
                if k not in pinned_keys]
    ap_mesh = [col.data for st in GLOBAL_MESH_CACHE._map.values()
               for col in st.columns.values()]
    ap_lane_devices = lane_devices(ap_local + ap_mesh)
    assert ap_lane_devices, "the AP leg cached no lane on any device"
    if not args.dry_run_cpu:
        assert all("tpu" in d.lower() for d in ap_lane_devices), \
            ap_lane_devices
    all_devs = sorted(str(d) for d in jax.devices())
    if dev["n_devices"] > 1:
        for a in ap_mesh:
            assert sorted(str(d) for d in a.sharding.device_set) == all_devs, \
                f"a sharded lane does not span all devices: {a.sharding}"
            assert len({s.device for s in a.addressable_shards}) == \
                dev["n_devices"], "shards share a device"
        assert ap_mesh, "no sharded lane was loaded on a multi-device host"
        for qid in AP_QUERIES:
            assert queries[f"q{qid}"]["engine"] == "mpp", queries
        assert inst.counters["mpp_fallback_local"] == 0
    reread = {k[2] for k in GLOBAL_DEVICE_CACHE._map if k not in pinned_keys
              and k[0] == inst.store("tpch", "lineitem").uid} & pinned_cols
    if dev["n_devices"] == 1 and not args.dry_run_cpu:
        # (on a CPU-only dry run the pin and the default device coincide)
        assert reread, "no AP scan re-read a lane the TP-pinned scan loaded"

    # -- TP leg --------------------------------------------------------------
    compiled_before_tp = ops.COMPILE_STATS["retraces"]
    n_tp = 4000
    rng = np.random.default_rng(args.seed)
    cents = rng.integers(0, 10_000_000, n_tp)
    model = {i: [f"owner{i % 97}", int(cents[i]), 0] for i in range(n_tp)}
    c.query("CREATE DATABASE smoke")
    c.query("USE smoke")
    c.query("CREATE TABLE acct (id BIGINT NOT NULL PRIMARY KEY, "
            "owner VARCHAR(32) NOT NULL, balance DECIMAL(15,2) NOT NULL, "
            "ver INT NOT NULL) PARTITION BY HASH(id) PARTITIONS 8")
    for lo in range(0, n_tp, 200):
        vals = ", ".join(
            f"({i}, '{model[i][0]}', {dec(model[i][1], 2)}, 0)"
            for i in range(lo, lo + 200))
        c.query(f"INSERT INTO acct (id, owner, balance, ver) VALUES {vals}")
    c2 = served.connect("smoke")

    def read(conn, i):
        _, rows = conn.query(
            f"SELECT id, owner, balance, ver FROM acct WHERE id = {i}")
        return [(int(r[0]), r[1], Decimal(r[2]), int(r[3])) for r in rows]

    def expect(i):
        o, b, v = model[i]
        return [(i, o, dec(b, 2), v)]

    probe = [int(i) for i in rng.choice(n_tp, 64, replace=False)]
    for i in probe:
        assert read(c, i) == expect(i), (i, read(c, i), expect(i))
    for i in probe[:16]:
        c.query(f"UPDATE acct SET balance = balance + 10.50, ver = ver + 1 "
                f"WHERE id = {i}")
        model[i][1] += 1050
        model[i][2] += 1
        assert read(c, i) == expect(i), ("own connection", i, read(c, i))
        assert read(c2, i) == expect(i), ("second connection", i, read(c2, i))
    ghost = n_tp + 7
    c.query("BEGIN")
    c.query(f"INSERT INTO acct (id, owner, balance, ver) "
            f"VALUES ({ghost}, 'ghost', 1.00, 0)")
    assert len(read(c, ghost)) == 1, "a transaction must see its own insert"
    assert read(c2, ghost) == [], "uncommitted insert visible to another conn"
    c.query("ROLLBACK")
    assert read(c, ghost) == [] and read(c2, ghost) == [], \
        "ROLLBACK left a row behind"
    _, agg = c2.query("SELECT COUNT(*), SUM(balance), SUM(ver) FROM acct")
    assert (int(agg[0][0]), Decimal(agg[0][1]), int(agg[0][2])) == (
        n_tp, dec(sum(m[1] for m in model.values()), 2), 16), agg
    tp_trace = [r[0] for r in c2.query("SHOW TRACE")[1]]
    tp_device = [ln.split(" ", 1)[1] for ln in tp_trace
                 if ln.startswith("exec-device ")]
    assert tp_device and all("cpu" in d.lower() for d in tp_device), tp_trace
    tp = {"correct": True, "rows_inserted": n_tp, "point_reads": len(probe),
          "updates_read_back_by_two_connections": 16,
          "rollback_left_no_row": True, "exec_device": tp_device,
          "programs_compiled": ops.COMPILE_STATS["retraces"] -
          compiled_before_tp}
    say("tp", **tp)
    c.close()
    c2.close()
    served.stop()

    # -- report --------------------------------------------------------------
    peak = hbm_high_water()  # {} on a backend without memory_stats (CPU)
    if not args.dry_run_cpu:
        assert len(peak) == dev["n_devices"] and all(peak.values()), peak
    report = {
        "ok": True,
        "sf": args.sf, "seed": args.seed, "rows_loaded": rows_loaded,
        "queries": queries, "tp": tp,
        "programs_compiled": ops.COMPILE_STATS["retraces"],
        "compile_wall_s_observed": round(
            ops.COMPILE_STATS["compile_ms"] / 1000, 1),
        "compile_wall_s_by_program_observed": {
            k: {"programs": n, "wall_s": round(ms / 1000, 1)}
            for k, (n, ms) in PROGRAMS.compile_ms_by_family().items()},
        "mpp_queries": inst.counters["mpp_queries"],
        "mpp_fallback_local": inst.counters["mpp_fallback_local"],
        "device_cache_bytes": GLOBAL_DEVICE_CACHE._bytes,
        "ap_lane_devices": ap_lane_devices,
        "tp_pinned_lane_columns_reread_by_ap": sorted(reread),
        "peak_bytes_in_use": peak,
        "native_available": native.AVAILABLE,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_before": cache_before,
        "compile_cache_entries_after": cache_entries(cache_dir),
        "total_wall_s_observed": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"chip_smoke_{dev['platform']}{dev['n_devices']}.json"),
            "w") as f:
        json.dump({**report, **tag}, f, indent=1)
    say("report", **report)
    # the contract's result line: exactly these keys, nothing after it
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["n_devices"]}}), flush=True)


if __name__ == "__main__":
    main()
