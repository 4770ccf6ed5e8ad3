"""Benchmark driver: TPC-H on the TPU engine vs a measured pandas host baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no numbers (BASELINE.md), so the baseline is measured on the
same machine and data: pandas (C-vectorized host columnar execution) standing in for
the reference's vectorized Java executor.  Metric: TPC-H Q1 rows/sec/chip, steady
state (plan cache + HBM-resident columns), best of N runs.

Env knobs: BENCH_SF (scale factor, default 0.2), BENCH_RUNS (default 3),
BENCH_QUERY (default 1).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if "--skew-only" in sys.argv and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the skew family needs the 8-virtual-device mesh; XLA reads this at
    # backend init, which has not happened yet at import time
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax

if os.environ.get("BENCH_PLATFORM"):
    # the one explicit way to get counts on a CPU (BENCH_PLATFORM=cpu)
    jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

from galaxysql_tpu import runtime

runtime.enable_compile_cache()
if not os.environ.get("BENCH_PLATFORM") and \
        jax.devices()[0].platform == "cpu":
    # no probe, no fallback: a backend that cannot start raised above with
    # its own error; a CPU default backend is refused unless asked for
    sys.exit("bench.py: the default JAX backend is 'cpu' — no accelerator "
             "result can come from it.  Set BENCH_PLATFORM=cpu to get "
             "counts (never rates) from a CPU run.")

from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES
from galaxysql_tpu.types import temporal


def load(sf: float):
    data = tpch.generate(sf)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    return inst, s, data


def pandas_q1(data):
    """Host baseline: pandas implementation of Q1 (vectorized C loops)."""
    import pandas as pd
    li = data["lineitem"]
    cutoff = temporal.parse_date("1998-12-01") - 90
    df = pd.DataFrame({
        "flag": li["l_returnflag"], "status": li["l_linestatus"],
        "qty": li["l_quantity"], "price": li["l_extendedprice"],
        "disc": li["l_discount"], "tax": li["l_tax"], "ship": li["l_shipdate"],
    })
    t0 = time.perf_counter()
    f = df[df.ship <= cutoff]
    disc_price = f.price * (1 - f.disc)
    charge = disc_price * (1 + f.tax)
    g = f.assign(disc_price=disc_price, charge=charge).groupby(
        ["flag", "status"], sort=True).agg(
        sum_qty=("qty", "sum"), sum_base=("price", "sum"),
        sum_disc=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), cnt=("qty", "size"))
    g = g.reset_index()
    return time.perf_counter() - t0, g


def pandas_q3(data):
    """Host baseline: pandas implementation of Q3 (3-way join + high-NDV agg)."""
    import pandas as pd
    cutoff = temporal.parse_date("1995-03-15")
    cust = pd.DataFrame({"ck": data["customer"]["c_custkey"],
                         "seg": data["customer"]["c_mktsegment"]})
    orders = pd.DataFrame({"ok": data["orders"]["o_orderkey"],
                           "ck": data["orders"]["o_custkey"],
                           "od": data["orders"]["o_orderdate"],
                           "sp": data["orders"]["o_shippriority"]})
    li = pd.DataFrame({"ok": data["lineitem"]["l_orderkey"],
                       "price": data["lineitem"]["l_extendedprice"],
                       "disc": data["lineitem"]["l_discount"],
                       "ship": data["lineitem"]["l_shipdate"]})
    t0 = time.perf_counter()
    c = cust[cust.seg == "BUILDING"][["ck"]]
    o = orders[orders.od < cutoff].merge(c, on="ck")
    l = li[li.ship > cutoff].merge(o[["ok", "od", "sp"]], on="ok")
    rev = l.price * (1 - l.disc)
    g = l.assign(rev=rev).groupby(["ok", "od", "sp"], sort=False).rev.sum()
    g = g.reset_index().sort_values(["rev", "od"],
                                    ascending=[False, True]).head(10)
    return time.perf_counter() - t0, g


def pandas_q5(data):
    """Host baseline: pandas Q5 (6-way shuffle join, BASELINE.md config 3)."""
    import pandas as pd
    lo = temporal.parse_date("1994-01-01")
    hi = temporal.parse_date("1995-01-01")
    region = pd.DataFrame({"rk": data["region"]["r_regionkey"],
                           "rn": data["region"]["r_name"]})
    nation = pd.DataFrame({"nk": data["nation"]["n_nationkey"],
                           "rk": data["nation"]["n_regionkey"],
                           "nn": data["nation"]["n_name"]})
    supp = pd.DataFrame({"sk": data["supplier"]["s_suppkey"],
                         "nk": data["supplier"]["s_nationkey"]})
    cust = pd.DataFrame({"ck": data["customer"]["c_custkey"],
                         "nk": data["customer"]["c_nationkey"]})
    orders = pd.DataFrame({"ok": data["orders"]["o_orderkey"],
                           "ck": data["orders"]["o_custkey"],
                           "od": data["orders"]["o_orderdate"]})
    li = pd.DataFrame({"ok": data["lineitem"]["l_orderkey"],
                       "sk": data["lineitem"]["l_suppkey"],
                       "price": data["lineitem"]["l_extendedprice"],
                       "disc": data["lineitem"]["l_discount"]})
    t0 = time.perf_counter()
    n = nation.merge(region[region.rn == "ASIA"][["rk"]], on="rk")
    s = supp.merge(n[["nk", "nn"]], on="nk")
    o = orders[(orders.od >= lo) & (orders.od < hi)]
    oc = o.merge(cust, on="ck")
    j = li.merge(oc[["ok", "nk"]], on="ok").merge(
        s, on="sk", suffixes=("_c", "_s"))
    j = j[j.nk_c == j.nk_s]
    rev = j.price * (1 - j.disc)
    g = j.assign(rev=rev).groupby("nn", sort=False).rev.sum()
    g = g.reset_index().sort_values("rev", ascending=False)
    return time.perf_counter() - t0, g


def pandas_q9(data):
    """Host baseline: pandas Q9 (product-type profit: 6-table join over
    high-NDV part/supplier keys — the runtime-filter probe-pruning shape)."""
    import pandas as pd
    part = pd.DataFrame({"pk": data["part"]["p_partkey"],
                         "pn": data["part"]["p_name"]})
    sup = pd.DataFrame({"sk": data["supplier"]["s_suppkey"],
                        "nk": data["supplier"]["s_nationkey"]})
    li = pd.DataFrame({"ok": data["lineitem"]["l_orderkey"],
                       "pk": data["lineitem"]["l_partkey"],
                       "sk": data["lineitem"]["l_suppkey"],
                       "qty": data["lineitem"]["l_quantity"],
                       "price": data["lineitem"]["l_extendedprice"],
                       "disc": data["lineitem"]["l_discount"]})
    ps = pd.DataFrame({"pk": data["partsupp"]["ps_partkey"],
                       "sk": data["partsupp"]["ps_suppkey"],
                       "cost": data["partsupp"]["ps_supplycost"]})
    orders = pd.DataFrame({"ok": data["orders"]["o_orderkey"],
                           "od": data["orders"]["o_orderdate"]})
    nation = pd.DataFrame({"nk": data["nation"]["n_nationkey"],
                           "nn": data["nation"]["n_name"]})
    t0 = time.perf_counter()
    pf = part[part.pn.str.contains("green")][["pk"]]
    j = li.merge(pf, on="pk").merge(sup, on="sk") \
          .merge(ps, on=["pk", "sk"]).merge(orders, on="ok") \
          .merge(nation, on="nk")
    amount = j.price * (1 - j.disc) - j.cost * j.qty
    year = pd.to_datetime(j.od, unit="D", origin="unix").dt.year
    g = j.assign(a=amount, y=year).groupby(["nn", "y"], sort=False).a.sum()
    g = g.reset_index().sort_values(["nn", "y"], ascending=[True, False])
    return time.perf_counter() - t0, g


def rf_probe_rows_delta(s, q):
    """Probe rows reaching join probe stages, runtime filters ON vs OFF —
    the pruning win the planned-filter pass buys, measured outside the timed
    loops (the counter adds a pre-bloom device sync per probe batch)."""
    from galaxysql_tpu.exec import runtime_filter as rfmod
    # fragment-cache cleared: a cached agg/build replay skips the probe
    # stages this delta exists to measure
    s.instance.frag_cache.clear()
    rfmod.reset_rf_stats(enabled=True)
    s.execute(q)
    on_rows = rfmod.RF_STATS["probe_rows"]
    built = rfmod.RF_STATS["filters_built"]
    s.instance.frag_cache.clear()
    rfmod.reset_rf_stats(enabled=True)
    s.execute("/*+TDDL:RUNTIME_FILTER(OFF)*/ " + q)
    off_rows = rfmod.RF_STATS["probe_rows"]
    rfmod.reset_rf_stats(enabled=False)
    return on_rows, off_rows, built


def pandas_ds_q7(d):
    """Host baseline: pandas TPC-DS q7 (5-way join + 4 avgs, config 5)."""
    import pandas as pd
    ss = pd.DataFrame({"sold": d["store_sales"]["ss_sold_date_sk"],
                       "item": d["store_sales"]["ss_item_sk"],
                       "cdemo": d["store_sales"]["ss_cdemo_sk"],
                       "promo": d["store_sales"]["ss_promo_sk"],
                       "qty": d["store_sales"]["ss_quantity"],
                       "lp": d["store_sales"]["ss_list_price"],
                       "coup": d["store_sales"]["ss_coupon_amt"],
                       "sp": d["store_sales"]["ss_sales_price"]})
    cd = pd.DataFrame({"cd": d["customer_demographics"]["cd_demo_sk"],
                       "g": d["customer_demographics"]["cd_gender"],
                       "m": d["customer_demographics"]["cd_marital_status"],
                       "e": d["customer_demographics"]["cd_education_status"]})
    dd = pd.DataFrame({"dk": d["date_dim"]["d_date_sk"],
                       "y": d["date_dim"]["d_year"]})
    it = pd.DataFrame({"ik": d["item"]["i_item_sk"],
                       "iid": d["item"]["i_item_id"]})
    pr = pd.DataFrame({"pk": d["promotion"]["p_promo_sk"],
                       "em": d["promotion"]["p_channel_email"],
                       "ev": d["promotion"]["p_channel_event"]})
    t0 = time.perf_counter()
    cdf = cd[(cd.g == "M") & (cd.m == "S") & (cd.e == "College")][["cd"]]
    prf = pr[(pr.em == "N") | (pr.ev == "N")][["pk"]]
    ddf = dd[dd.y == 2000][["dk"]]
    j = ss.merge(ddf, left_on="sold", right_on="dk") \
          .merge(it, left_on="item", right_on="ik") \
          .merge(cdf, left_on="cdemo", right_on="cd") \
          .merge(prf, left_on="promo", right_on="pk")
    g = j.groupby("iid", sort=True).agg(a1=("qty", "mean"), a2=("lp", "mean"),
                                        a3=("coup", "mean"), a4=("sp", "mean"))
    g = g.reset_index().head(100)
    return time.perf_counter() - t0, g


def kernel_microbench(data, platform: str, runs: int):
    """Device-kernel roofline datapoint: the Q1 aggregation kernel alone over
    device-resident lanes — rows/s and GB/s (lanes actually touched), so the
    first round where the TPU backend answers yields an MFU/roofline number,
    not just end-to-end times."""
    import jax
    import jax.numpy as jnp
    li = data["lineitem"]
    cutoff = temporal.parse_date("1998-12-01") - 90
    lanes = {
        "ship": jnp.asarray(np.asarray(li["l_shipdate"])),
        "qty": jnp.asarray(np.asarray(li["l_quantity"])),
        "price": jnp.asarray(np.asarray(li["l_extendedprice"])),
        "disc": jnp.asarray(np.asarray(li["l_discount"])),
        "tax": jnp.asarray(np.asarray(li["l_tax"])),
        "flag": jnp.asarray(np.unique(np.asarray(li["l_returnflag"]),
                                      return_inverse=True)[1].astype(np.int32)),
    }

    @jax.jit
    def q1_kernel(ship, qty, price, disc, tax, flag):
        live = ship <= cutoff
        disc_price = price * (1 - disc)
        charge = disc_price * (1 + tax)
        seg = jnp.where(live, flag.astype(jnp.int32), 8)
        out = []
        for lane in (qty, price, disc_price, charge, disc,
                     jnp.ones_like(qty)):
            out.append(jax.ops.segment_sum(jnp.where(live, lane, 0), seg,
                                           num_segments=9))
        return out

    args = (lanes["ship"], lanes["qty"], lanes["price"], lanes["disc"],
            lanes["tax"], lanes["flag"])
    jax.block_until_ready(q1_kernel(*args))  # compile
    best = None
    for _ in range(max(runs, 3)):
        t0 = time.perf_counter()
        jax.block_until_ready(q1_kernel(*args))
        el = time.perf_counter() - t0
        best = el if best is None or el < best else best
    n = int(lanes["qty"].shape[0])
    nbytes = sum(int(a.nbytes) for a in args)
    return {
        "metric": f"q1_kernel_{platform}_bandwidth",
        "value": round(nbytes / best / 1e9, 2), "unit": "GB/s",
        "vs_baseline": round(n / best / 1e6, 1),  # Mrows/s alongside
        "platform": platform,
    }


def dispatch_microbench(runs: int):
    """Per-batch dispatch overhead: a filter→project chain over B device
    batches, stacked per-operator programs vs ONE fused segment program.

    Reports fused ms/batch; vs_baseline = unfused/fused wall ratio; plus the
    measured streaming-program dispatch counts per batch for both shapes (the
    number the fusion PR moves: 2 dispatches/batch -> 1)."""
    import jax.numpy as jnp
    from galaxysql_tpu.chunk.batch import Column, ColumnBatch
    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.exec.fusion import FusedPipelineOp, FusedSegment
    from galaxysql_tpu.exec.operators import FilterOp, ProjectOp, SourceOp
    from galaxysql_tpu.expr import ir
    from galaxysql_tpu.types import datatype as dt

    B, n = 32, 1 << 17  # device path (capacity > TP_HOST_ROWS)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(B):
        a = jnp.asarray(rng.integers(0, 1 << 20, n))
        b = jnp.asarray(rng.random(n))
        batches.append(ColumnBatch({"a": Column(a, None, dt.BIGINT, None),
                                    "b": Column(b, None, dt.DOUBLE, None)}, None))
    ca = ir.ColRef("a", dt.BIGINT, None)
    cb = ir.ColRef("b", dt.DOUBLE, None)
    pred = ir.call("lt", ca, ir.lit(1 << 19))
    projs = [("c", ir.call("mul", cb, ir.lit(2.0))), ("a", ca)]

    def drain(op):
        last = None
        for out in op.batches():
            last = out.live_mask()
        jax.block_until_ready(last)

    def timed(make):
        drain(make())  # warmup: compile
        ops.reset_dispatch_stats()
        drain(make())
        d_per_batch = ops.DISPATCH_STATS["dispatches"] / B
        best = None
        for _ in range(max(runs, 3)):
            t0 = time.perf_counter()
            drain(make())
            el = time.perf_counter() - t0
            best = el if best is None or el < best else best
        return best / B, d_per_batch

    # both shapes construct their operators inside the timed drain, so each
    # side pays its own per-execution setup (expression walks, cache-key
    # resolution) and the ratio isolates the per-batch dispatch difference
    unfused_ms, unfused_d = timed(
        lambda: ProjectOp(FilterOp(SourceOp(batches), pred), projs))
    fused_ms, fused_d = timed(lambda: FusedPipelineOp(
        SourceOp(batches),
        FusedSegment([("filter", pred), ("project", list(projs))])))
    return {
        "metric": "pipeline_fused_dispatch_ms_per_batch",
        "value": round(fused_ms * 1000, 4), "unit": "ms/batch",
        "vs_baseline": round(unfused_ms / fused_ms, 3),
        "fused_dispatches_per_batch": fused_d,
        "unfused_dispatches_per_batch": unfused_d,
        "platform": jax.devices()[0].platform,
    }


def _closed_loop_point(inst, tpl, keys, n_sessions, per_session):
    """Closed-loop multi-session point-select driver (thin wrapper over the
    generic `_closed_loop_ops` scaffolding).  Returns (qps, p99_ms, errors)."""
    nkeys = len(keys)
    return _closed_loop_ops(
        inst, "tpch", n_sessions, per_session,
        lambda sx, i, j: sx.execute(tpl % keys[(i * 31 + j * 7) % nkeys]))


def batch_serving_bench(inst, s, data, platform):
    """Mega-batched TP serving: closed-loop QPS/chip + p99 at increasing
    concurrent-session counts, batching on (adaptive window) vs off (the
    PR-5 sequential fast path) on the SAME engine + data.  vs_baseline is
    the batching-on/off QPS ratio — the launch-amortization win this PR
    claims — and retraces_steady guards the static batch shapes (steady
    state must compile NOTHING).

    Methodology: best of BENCH_BATCH_RUNS (default 3) closed-loop passes per
    mode per level, matching the suite's best-of-runs convention — the
    closed loop is scheduler-sensitive, and a single pass mostly measures
    the ramp while the group-commit pipeline converges.  The default top
    level is 4000 sessions: 10k CPython threads exceed what small
    containers allow (set BENCH_BATCH_SESSIONS=100,1000,10000 on a real
    host — the driver itself is ready for it)."""
    from galaxysql_tpu.exec import operators as _ops
    from galaxysql_tpu.utils.metrics import BATCH_GROUP_SIZE

    okeys = data["orders"]["o_orderkey"]
    keys = [int(k) for k in okeys[:: max(1, len(okeys) // 4096)]]
    tpl = "select o_totalprice from orders where o_orderkey = %d"
    s.execute(tpl % keys[0])  # register + warm the PointPlan
    s.execute(tpl % keys[0])
    levels = [int(x) for x in os.environ.get(
        "BENCH_BATCH_SESSIONS", "100,1000,4000").split(",") if x]
    reps = max(1, int(os.environ.get("BENCH_BATCH_RUNS", "3")))
    out = []
    # warm both paths + the group-commit pipeline before any timed pass
    inst.config.set_instance("ENABLE_BATCH_SCHEDULER", 1)
    _closed_loop_point(inst, tpl, keys, 64, 4)
    inst.config.set_instance("ENABLE_BATCH_SCHEDULER", 0)
    _closed_loop_point(inst, tpl, keys, 64, 4)
    for n in levels:
        per = max(4, min(16, 16000 // n))
        inst.config.set_instance("ENABLE_BATCH_SCHEDULER", 0)
        off_runs = []
        for _ in range(reps):
            qps, p99, errs = _closed_loop_point(inst, tpl, keys, n, per)
            if errs:
                raise errs[0]
            off_runs.append((qps, p99))
        qps_off, p99_off = max(off_runs)
        inst.config.set_instance("ENABLE_BATCH_SCHEDULER", 1)
        _closed_loop_point(inst, tpl, keys, n, 2)  # ramp the pipeline
        _ops.reset_compile_stats()
        BATCH_GROUP_SIZE.reset()  # per-level quantiles: no warmup/prior-level blend
        on_runs = []
        for _ in range(reps):
            qps, p99, errs = _closed_loop_point(inst, tpl, keys, n, per)
            if errs:
                raise errs[0]
            on_runs.append((qps, p99))
        qps_on, p99_on = max(on_runs)
        gs = BATCH_GROUP_SIZE.quantiles()
        out.append({
            "metric": f"tp_point_select_qps_per_chip_{n}_sessions",
            "value": round(qps_on, 1), "unit": "qps",
            "vs_baseline": round(qps_on / max(qps_off, 1e-9), 3),
            "p99_ms": round(p99_on, 3),
            "unbatched_qps": round(qps_off, 1),
            "unbatched_p99_ms": round(p99_off, 3),
            "batch_flushes": BATCH_GROUP_SIZE.count,
            "batch_group_p50": gs[0.5],
            "retraces_steady": _ops.COMPILE_STATS["retraces"],
            "platform": platform,
        })
    return out


def _closed_loop_ops(inst, schema, n_sessions, per_session, op):
    """Closed-loop multi-session driver over an arbitrary per-op callable
    `op(sx, i, j)` — THE scaffolding (`_closed_loop_point` wraps it):
    sessions + threads built before the clock starts, shrunken stacks,
    bounded ready-wait.  Returns (qps, p99_ms, errors)."""
    import threading
    lats: list = []
    errors: list = []
    lock = threading.Lock()
    start = threading.Event()
    all_ready = threading.Event()
    ready = [0]

    def run(i):
        counted = False
        try:
            sx = Session(inst, schema=schema)
            mine = []
            with lock:
                ready[0] += 1
                counted = True
                if ready[0] == n_sessions:
                    all_ready.set()
            start.wait()
            for j in range(per_session):
                t0 = time.perf_counter()
                op(sx, i, j)
                mine.append(time.perf_counter() - t0)
            sx.close()
            with lock:
                lats.extend(mine)
        except Exception as e:  # pragma: no cover - surfaced to the caller
            with lock:
                errors.append(e)
                if not counted:
                    ready[0] += 1
                    if ready[0] == n_sessions:
                        all_ready.set()

    old_stack = threading.stack_size(512 << 10)
    try:
        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(n_sessions)]
        for t in threads:
            t.start()
    finally:
        threading.stack_size(old_stack)
    all_ready.wait(timeout=120.0)
    t0 = time.perf_counter()
    start.set()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors or not lats:
        return 0.0, 0.0, errors
    lats.sort()
    p99 = lats[min(int(0.99 * len(lats)), len(lats) - 1)]
    return len(lats) / wall, p99 * 1000.0, errors


def dml_serving_bench(inst, s, platform):
    """Mega-batched write serving: closed-loop DML QPS/chip + p99 at
    increasing session counts, DML batching on (adaptive window, group
    commit, coalesced CDC) vs off (the sequential per-statement path) on the
    SAME engine.  vs_baseline is the on/off QPS ratio — the write-path
    amortization win this PR claims.  A mixed 50/50 read+write closed loop
    rides along (`tp_mixed_rw_qps_...`): real TP traffic is never
    write-only, and the two batchers must compose.

    Methodology matches batch_serving_bench: best of BENCH_DML_RUNS
    (default 3) passes per mode per level; every INSERT id is globally
    unique so no pass ever conflicts with another."""
    from galaxysql_tpu.exec import operators as _ops
    from galaxysql_tpu.utils.metrics import DML_GROUP_SIZE

    schema = "dmlbench"
    # measure the batcher, not the shedder: the closed loop intentionally
    # saturates, and AIMD shedding typed errors would abort the pass.
    # Both knobs restore on exit — later bench sections (and operator
    # settings) must not inherit this section's configuration.
    prev_adm = inst.config.get("ENABLE_ADMISSION_CONTROL")
    prev_batch = inst.config.get("ENABLE_DML_BATCHING")
    inst.config.set_instance("ENABLE_ADMISSION_CONTROL", 0)
    try:
        return _dml_serving_passes(inst, s, schema, platform)
    finally:
        inst.config.set_instance("ENABLE_DML_BATCHING", prev_batch)
        inst.config.set_instance("ENABLE_ADMISSION_CONTROL", prev_adm)


def _dml_serving_passes(inst, s, schema, platform):
    from galaxysql_tpu.exec import operators as _ops
    from galaxysql_tpu.utils.metrics import DML_GROUP_SIZE
    try:
        s.execute(f"CREATE DATABASE {schema}")
    except Exception:
        pass
    sb = Session(inst, schema=schema)
    sb.execute("CREATE TABLE wb (id BIGINT NOT NULL PRIMARY KEY, "
               "grp INT NOT NULL, amt DECIMAL(12,2)) "
               "PARTITION BY HASH(id) PARTITIONS 4")
    ins = "INSERT INTO wb (id, grp, amt) VALUES (%d, %d, %d.25)"
    sel = "SELECT amt FROM wb WHERE id = %d"
    # register + warm the DML batch plan and the read PointPlan
    sb.execute(ins % (1, 1, 1))
    sb.execute(ins % (2, 2, 2))
    sb.execute(sel % 1)
    sb.execute(sel % 1)
    next_id = [1000]

    def make_insert_op(base):
        def op(sx, i, j):
            k = base + i * 1000 + j
            sx.execute(ins % (k, k % 97, k % 1000))
        return op

    def make_mixed_op(base):
        def op(sx, i, j):
            k = base + i * 1000 + j
            if j % 2 == 0:
                sx.execute(ins % (k, k % 97, k % 1000))
            else:
                sx.execute(sel % (base + i * 1000 + j - 1))
        return op

    levels = [int(x) for x in os.environ.get(
        "BENCH_DML_SESSIONS", "64,256").split(",") if x]
    reps = max(1, int(os.environ.get("BENCH_DML_RUNS", "3")))
    out = []

    def passes(mode_on, mk_op, n, per):
        inst.config.set_instance("ENABLE_DML_BATCHING", 1 if mode_on else 0)
        best = (0.0, 0.0)
        for _ in range(reps):
            base = next_id[0]
            next_id[0] += n * 1000 + 1000
            qps, p99, errs = _closed_loop_ops(inst, schema, n, per,
                                              mk_op(base))
            if errs:
                raise errs[0]
            if qps > best[0]:
                best = (qps, p99)
        return best

    # warm both paths + the group-commit pipeline before any timed pass
    passes(True, make_insert_op, 32, 4)
    passes(False, make_insert_op, 32, 4)
    for n in levels:
        per = max(4, min(16, 8000 // n))
        qps_off, p99_off = passes(False, make_insert_op, n, per)
        _ops.reset_compile_stats()
        DML_GROUP_SIZE.reset()
        qps_on, p99_on = passes(True, make_insert_op, n, per)
        gs = DML_GROUP_SIZE.quantiles()
        out.append({
            "metric": f"tp_dml_qps_per_chip_{n}_sessions",
            "value": round(qps_on, 1), "unit": "qps",
            "vs_baseline": round(qps_on / max(qps_off, 1e-9), 3),
            "p99_ms": round(p99_on, 3),
            "unbatched_qps": round(qps_off, 1),
            "unbatched_p99_ms": round(p99_off, 3),
            "dml_flushes": DML_GROUP_SIZE.count,
            "dml_group_p50": gs[0.5],
            "retraces_steady": _ops.COMPILE_STATS["retraces"],
            "platform": platform,
        })
        mq_off, mp_off = passes(False, make_mixed_op, n, per)
        mq_on, mp_on = passes(True, make_mixed_op, n, per)
        out.append({
            "metric": f"tp_mixed_rw_qps_per_chip_{n}_sessions",
            "value": round(mq_on, 1), "unit": "qps",
            "vs_baseline": round(mq_on / max(mq_off, 1e-9), 3),
            "p99_ms": round(mp_on, 3),
            "unbatched_qps": round(mq_off, 1),
            "unbatched_p99_ms": round(mp_off, 3),
            "platform": platform,
        })
    sb.close()
    return out


def _bench_query(s, q, runs):
    best, _d, _c = _bench_query_d(s, q, runs)
    return best


def _profile_summary(s, q):
    """One profiled execution -> {operator: rows/ms} summary attached to the
    BENCH json, so the perf trajectory records WHERE time went (per-operator,
    per-segment), not just end-to-end totals.  Runs OUTSIDE the timed loops:
    profiling forces device syncs the benchmark numbers must not contain."""
    try:
        s.execute("SET ENABLE_QUERY_PROFILING = 1")
        s.execute(q)
        prof = s.instance.profiles.entries()[-1]
        return {
            "trace_id": prof.trace_id,
            "engine": prof.engine,
            "elapsed_ms": prof.elapsed_ms,
            "operators": [
                {"op": st["operator"], "rows": st["rows_out"],
                 "ms": st["wall_ms"],
                 **({"fused": st["segment"]} if st.get("fused") else {})}
                for st in prof.op_stats],
            "segments": [
                {"chain": sp.chain, "rows_in": sp.rows_in,
                 "rows_out": sp.rows_out, "ms": sp.wall_ms}
                for sp in prof.segments],
        }
    except Exception as e:  # profile datapoint is best-effort
        return {"error": str(e)}
    finally:
        s.execute("SET ENABLE_QUERY_PROFILING = 0")


def _bench_query_d(s, q, runs):
    """(best wall seconds, steady-state streaming dispatches per execution,
    compile stats).

    The dispatch count is the number the fusion pass moves (deterministic,
    unlike wall time on a shared host): one streaming-program invocation per
    batch per segment — an XLA dispatch on the device path, a host-np program
    call on the TP path.  Compile stats bracket the warmup (cold trace+compile
    cost of the query's program set) and the timed loop (steady-state
    retraces, which a healthy lifted-key cache keeps at ZERO — a regression
    here means some program's key became value-sensitive)."""
    from galaxysql_tpu.exec import operators as _ops

    def _frag_clear():
        # these metrics track ENGINE throughput across PRs: clear the
        # fragment cache per run so a cached replay doesn't masquerade as a
        # faster pipeline (the *_warm_* metrics measure the cached state)
        fcache = getattr(s.instance, "frag_cache", None)
        if fcache is not None:
            fcache.clear()
    _ops.reset_compile_stats()
    s.execute(q)  # warmup: compile + populate device cache
    compile_stats = {
        "compile_ms": round(_ops.COMPILE_STATS["compile_ms"], 3),
        "retrace_count": _ops.COMPILE_STATS["retraces"],
    }
    times = []
    _frag_clear()
    _ops.reset_dispatch_stats()
    _ops.reset_compile_stats()
    t0 = time.perf_counter()
    s.execute(q)
    times.append(time.perf_counter() - t0)
    dispatches = _ops.DISPATCH_STATS["dispatches"]
    for _ in range(runs - 1):
        _frag_clear()
        t0 = time.perf_counter()
        s.execute(q)
        times.append(time.perf_counter() - t0)
    compile_stats["retraces_steady"] = _ops.COMPILE_STATS["retraces"]
    return min(times), dispatches, compile_stats


def skew_bench(platform):
    """Zipf theta sweep on a Q9-like join family over the 8-device mesh:
    skew-aware execution on vs SKEW(OFF), per-theta rows/sec/chip plus the
    observed shard-skew ratio (max/mean live rows per shard of the join
    stage) and steady-state retrace counts.

    The Q9 shape: a Zipf-keyed fact joining two dimension tables sized above
    the broadcast threshold (so both joins hash-shuffle — the skew-sensitive
    plan), feeding a grouped aggregate.  rows/sec/chip divides by the mesh
    size: the 8 virtual devices share this host's cores."""
    from galaxysql_tpu.exec import operators as _ops
    from galaxysql_tpu.parallel.mesh import make_mesh
    from galaxysql_tpu.parallel.mpp import MppExecutor
    from galaxysql_tpu.plan.physical import ExecContext
    from galaxysql_tpu.server.instance import Instance
    from galaxysql_tpu.server.session import Session

    S = 8
    n = int(os.environ.get("BENCH_SKEW_ROWS", str(2_000_000)))
    k = int(os.environ.get("BENCH_SKEW_KEYS", str(600_000)))
    reps = max(1, int(os.environ.get("BENCH_SKEW_RUNS", "3")))
    rng = np.random.default_rng(17)
    mesh = make_mesh(S)
    out = []
    q = ("SELECT d.attr, d2.attr, COUNT(*), SUM(f.v) "
         "FROM fact f, dim d, dim2 d2 "
         "WHERE f.k = d.k AND f.k2 = d2.k GROUP BY d.attr, d2.attr")

    # theta sweep per the Zipf literature (top-key mass ~19% at theta=1.2)
    # plus the production hot-key-incident shape: ONE key holding 35% — the
    # case the off path's overflow ladder hurts most
    for theta, label in ((0.0, "theta0"), (0.8, "theta08"),
                         (1.2, "theta12"), ("hot", "hotkey35")):
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE skb; USE skb")
        s.execute("CREATE TABLE fact (id BIGINT PRIMARY KEY, k BIGINT, "
                  "k2 BIGINT, v BIGINT) PARTITION BY HASH(id) PARTITIONS 8")
        if theta == "hot":
            p = np.full(k, 0.65 / (k - 1))
            p[7] = 0.35
            keys = rng.choice(k, size=n, p=p)
            keys2 = rng.choice(k, size=n, p=p)
        elif theta > 0:
            p = np.arange(1, k + 1, dtype=np.float64) ** -theta
            p /= p.sum()
            keys = rng.choice(k, size=n, p=p)
            keys2 = rng.choice(k, size=n, p=p)
        else:
            keys = rng.integers(0, k, size=n)
            keys2 = rng.integers(0, k, size=n)
        inst.store("skb", "fact").insert_arrays(
            {"id": np.arange(n, dtype=np.int64),
             "k": keys.astype(np.int64), "k2": keys2.astype(np.int64),
             "v": rng.integers(0, 1000, size=n).astype(np.int64)},
            inst.tso.next_timestamp())
        for dim, mul in (("dim", 7919), ("dim2", 104729)):
            s.execute(f"CREATE TABLE {dim} (did BIGINT PRIMARY KEY, "
                      "k BIGINT, attr BIGINT) "
                      "PARTITION BY HASH(did) PARTITIONS 8")
            inst.store("skb", dim).insert_arrays(
                {"did": (np.arange(k, dtype=np.int64) * mul) % (1 << 30),
                 "k": np.arange(k, dtype=np.int64),
                 "attr": np.arange(k, dtype=np.int64) % 11},
                inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE fact, dim, dim2")

        def once(sql, collect=False):
            plan = inst.planner.plan_select(sql, "skb")
            ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                              archive=inst.archive, archive_instance=inst,
                              hints=plan.hints)
            ctx.collect_stats = collect
            t0 = time.perf_counter()
            MppExecutor(ctx, mesh).execute(plan.rel)
            return time.perf_counter() - t0, ctx

        def best(sql):
            once(sql)  # compile warmup
            _ops.reset_compile_stats()
            ts = []
            for _ in range(reps):
                inst.frag_cache.clear()
                ts.append(once(sql)[0])
            return min(ts), _ops.COMPILE_STATS["retraces"]

        t_on, retraces = best(q)
        t_off, _ = best("/*+TDDL: SKEW(OFF)*/ " + q)
        # shard-skew ratio of the join stages, measured on the OFF path (the
        # imbalance the hybrid removes); profiled run, excluded from timing
        inst.frag_cache.clear()
        _, ctx = once("/*+TDDL: SKEW(OFF)*/ " + q, collect=True)
        ratios = [st["shard_skew"] for st in ctx.op_stats
                  if st.get("shard_skew")]
        _, ctx_on = once(q)
        out.append({
            "metric": f"tpch_q9_skew_{label}_rows_per_sec_per_chip",
            "value": round(n / t_on / S, 1), "unit": "rows/s",
            "vs_skew_off": round(t_off / t_on, 3),
            "skew_off_rows_per_sec_per_chip": round(n / t_off / S, 1),
            "shard_skew_ratio_off": max(ratios) if ratios else None,
            "hybrid_engaged": any("mpp-hybrid-join" in t
                                  for t in ctx_on.trace),
            "salted": any("mpp-salted-agg" in t for t in ctx_on.trace),
            "retraces_steady": retraces, "theta": theta,
            "platform": platform, "mesh": S,
        })
        s.close()
    return out


def skew_only_main():
    """`bench.py --skew-only` (make bench-skew): the Zipf theta sweep on the
    8-virtual-device mesh."""
    for line in skew_bench(jax.devices()[0].platform):
        print(json.dumps(line))


def main():
    sf = float(os.environ.get("BENCH_SF", "0.2"))
    runs = int(os.environ.get("BENCH_RUNS", "3"))
    platform = jax.devices()[0].platform

    inst, s, data = load(sf)
    n_rows = len(data["lineitem"]["l_orderkey"])
    results = []

    # -- TP point-query latency (BASELINE.md config 1's latency floor) --------
    import pandas as pd
    okeys = data["orders"]["o_orderkey"]
    probe_keys = [int(okeys[i]) for i in
                  np.linspace(0, len(okeys) - 1, 21).astype(int)]
    odf = pd.DataFrame({"ok": okeys, "tp": data["orders"]["o_totalprice"]})
    point = "select o_totalprice from orders where o_orderkey = %d"
    _bench_query(s, point % probe_keys[0], 1)  # compile once
    lats, base_lats = [], []
    for k in probe_keys:
        t0 = time.perf_counter()
        s.execute(point % k)
        lats.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _ = odf.tp.values[odf.ok.values == k]
        base_lats.append(time.perf_counter() - t0)
    lat = sorted(lats)[len(lats) // 2]
    base_lat = sorted(base_lats)[len(base_lats) // 2]
    from galaxysql_tpu.exec import operators as _ops
    _ops.reset_dispatch_stats()
    s.execute(point % probe_keys[0])
    results.append({
        "metric": f"tp_point_select_p50_latency_sf{sf:g}",
        "value": round(lat * 1000, 3), "unit": "ms",
        "vs_baseline": round(base_lat / lat, 3), "platform": platform,
        "dispatches_per_exec": _ops.DISPATCH_STATS["dispatches"],
    })

    # -- mega-batched TP serving: closed-loop multi-session QPS ---------------
    if os.environ.get("BENCH_BATCH", "1") != "0":
        results.extend(batch_serving_bench(inst, s, data, platform))

    # -- mega-batched write serving: closed-loop DML + mixed r/w QPS ----------
    if os.environ.get("BENCH_DML", "1") != "0":
        results.extend(dml_serving_bench(inst, s, platform))

    # -- skew-aware execution: Zipf theta sweep on Q9-like joins --------------
    # needs the 8-device mesh; single-device runs use `bench.py --skew-only`
    # (which forces 8 virtual CPU devices) / `make bench-skew`
    if os.environ.get("BENCH_SKEW", "1") != "0" and len(jax.devices()) >= 8:
        try:
            results.extend(skew_bench(platform))
        except Exception as e:
            # best-effort (headline lines still print) but never silent: a
            # dashboard must see WHY the tpch_q9_skew_* lines disappeared
            print(f"skew bench failed: {e!r}", file=sys.stderr)

    # -- TPC-H Q3: 3-way join + high-NDV agg + top-n ---------------------------
    q3_best, q3_d, q3_c = _bench_query_d(s, QUERIES[3], runs)
    q3_base = min(pandas_q3(data)[0] for _ in range(runs))
    results.append({
        "metric": f"tpch_q3_sf{sf:g}_rows_per_sec_per_chip",
        "value": round(n_rows / q3_best, 1), "unit": "rows/s",
        "vs_baseline": round(q3_base / q3_best, 3), "platform": platform,
        "dispatches_per_exec": q3_d, "compile": q3_c,
        "profile": _profile_summary(s, QUERIES[3]),
    })

    # -- TPC-H Q5: 6-way shuffle join (config 3) -------------------------------
    q5_best, q5_d, q5_c = _bench_query_d(s, QUERIES[5], runs)
    q5_base = min(pandas_q5(data)[0] for _ in range(runs))
    results.append({
        "metric": f"tpch_q5_sf{sf:g}_rows_per_sec_per_chip",
        "value": round(n_rows / q5_best, 1), "unit": "rows/s",
        "vs_baseline": round(q5_base / q5_best, 3), "platform": platform,
        "dispatches_per_exec": q5_d, "compile": q5_c,
        "profile": _profile_summary(s, QUERIES[5]),
    })

    # -- runtime-filter pruning win: probe rows scanned, filters on vs off ----
    on_rows, off_rows, built = rf_probe_rows_delta(s, QUERIES[5])
    results.append({
        "metric": f"tpch_q5_sf{sf:g}_rf_probe_rows_delta",
        "value": round(off_rows / max(on_rows, 1), 3), "unit": "x",
        "vs_baseline": round(off_rows / max(on_rows, 1), 3),
        "probe_rows_filters_on": on_rows,
        "probe_rows_filters_off": off_rows,
        "filters_built": built, "platform": platform,
    })

    # -- TPC-H Q9: 6-table product-profit join (runtime-filter headline) -------
    q9_best, q9_d, q9_c = _bench_query_d(s, QUERIES[9], runs)
    q9_base = min(pandas_q9(data)[0] for _ in range(runs))
    results.append({
        "metric": f"tpch_q9_sf{sf:g}_rows_per_sec_per_chip",
        "value": round(n_rows / q9_best, 1), "unit": "rows/s",
        "vs_baseline": round(q9_base / q9_best, 3), "platform": platform,
        "dispatches_per_exec": q9_d, "compile": q9_c,
        "profile": _profile_summary(s, QUERIES[9]),
    })

    # -- fragment cache: warm (second-execution) steady state ------------------
    # cold = fragment cache cleared before each run (kernels compiled, device
    # cache warm — isolates the build-side work the cache removes); warm =
    # repeated executions hitting the cached build artifacts + filters.  The
    # steady-state number a CN serving parameterized traffic actually sees.
    fcache = inst.frag_cache
    for qid in (5, 9):
        q = QUERIES[qid]
        s.execute(q)  # compile + device-cache warmup (cache cleared below)
        cold_times = []
        for _ in range(runs):
            fcache.clear()
            t0 = time.perf_counter()
            s.execute(q)
            cold_times.append(time.perf_counter() - t0)
        cold = min(cold_times)
        s.execute(q)  # populate the fragment cache
        h0, m0 = fcache.hits, fcache.misses
        warm_times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            s.execute(q)
            warm_times.append(time.perf_counter() - t0)
        warm = min(warm_times)
        hits = fcache.hits - h0
        lookups = hits + (fcache.misses - m0)
        results.append({
            "metric": f"tpch_q{qid}_sf{sf:g}_warm_rows_per_sec_per_chip",
            "value": round(n_rows / warm, 1), "unit": "rows/s",
            # vs_baseline here = warm speedup over the cold (cache-cleared)
            # run of the SAME engine: the build + filter reuse win
            "vs_baseline": round(cold / warm, 3),
            "cold_rows_per_sec": round(n_rows / cold, 1),
            "cache_hit_rate": round(hits / max(lookups, 1), 3),
            "cache_bytes": fcache.bytes, "platform": platform,
        })

    # -- TPC-DS q7: 5-way star join + 4 avgs (config 5) ------------------------
    if os.environ.get("BENCH_TPCDS", "1") != "0":
        from galaxysql_tpu.storage import tpcds
        ddata = tpcds.generate(sf / 2)
        s.execute("CREATE DATABASE tpcds")
        s.execute("USE tpcds")
        for t in tpcds.TABLE_ORDER:
            s.execute(tpcds.TPCDS_DDL[t])
            inst.store("tpcds", t).insert_pylists(ddata[t],
                                                  inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE " + ", ".join(tpcds.TABLE_ORDER))
        ds_best, ds_d, ds_c = _bench_query_d(s, tpcds.QUERIES["q7"], runs)
        ds_base = min(pandas_ds_q7(ddata)[0] for _ in range(runs))
        n_ss = len(ddata["store_sales"]["ss_item_sk"])
        results.append({
            "metric": f"tpcds_q7_sf{sf / 2:g}_rows_per_sec_per_chip",
            "value": round(n_ss / ds_best, 1), "unit": "rows/s",
            "vs_baseline": round(ds_base / ds_best, 3), "platform": platform,
            "dispatches_per_exec": ds_d, "compile": ds_c,
            "profile": _profile_summary(s, tpcds.QUERIES["q7"]),
        })
        s.execute("USE tpch")

    # -- SSB Q1.1: fact scan + date-dim join + filtered agg (config 4) ----------
    if os.environ.get("BENCH_SSB", "1") != "0":
        from galaxysql_tpu.storage import ssb
        sdata = ssb.generate(sf / 2)
        s.execute("CREATE DATABASE ssb")
        s.execute("USE ssb")
        for t in ssb.TABLE_ORDER:
            s.execute(ssb.SSB_DDL[t])
            inst.store("ssb", t).insert_arrays(sdata[t],
                                               inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE " + ", ".join(ssb.TABLE_ORDER))
        ssb_best, ssb_d, ssb_c = _bench_query_d(s, ssb.QUERIES["1.1"], runs)

        def pandas_ssb(d):
            lo, da = d["lineorder"], d["dates"]
            # frames build OUTSIDE the timer (the engine's lanes preload too)
            dd = pd.DataFrame({"dk": da["d_datekey"], "y": da["d_year"]})
            lf = pd.DataFrame({"od": lo["lo_orderdate"],
                               "p": lo["lo_extendedprice"],
                               "disc": lo["lo_discount"], "q": lo["lo_quantity"]})
            t0 = time.perf_counter()
            f = lf[(lf.disc >= 1) & (lf.disc <= 3) & (lf.q < 25)]
            j = f.merge(dd[dd.y == 1993], left_on="od", right_on="dk")
            _ = (j.p * j.disc).sum()
            return time.perf_counter() - t0

        ssb_base = min(pandas_ssb(sdata) for _ in range(runs))
        n_lo = len(sdata["lineorder"]["lo_orderdate"])
        results.append({
            "metric": f"ssb_q1.1_sf{sf / 2:g}_rows_per_sec_per_chip",
            "value": round(n_lo / ssb_best, 1), "unit": "rows/s",
            "vs_baseline": round(ssb_base / ssb_best, 3), "platform": platform,
            "dispatches_per_exec": ssb_d, "compile": ssb_c,
            "profile": _profile_summary(s, ssb.QUERIES["1.1"]),
        })
        s.execute("USE tpch")

    # -- SF>=1 config (BASELINE.md intent: the baselines target SF1-100): Q1 +
    # Q3 on a 6M-row lineitem, loaded fresh so the small-SF frames can be GC'd
    big_sf = float(os.environ.get("BENCH_SF_BIG", "1"))
    if big_sf > 0:
        del data
        inst, s, data = load(big_sf)  # headline Q1 below runs at this scale
        nb = len(data["lineitem"]["l_orderkey"])
        q3b_best = _bench_query(s, QUERIES[3], runs)
        q3b_base = min(pandas_q3(data)[0] for _ in range(runs))
        results.append({
            "metric": f"tpch_q3_sf{big_sf:g}_rows_per_sec_per_chip",
            "value": round(nb / q3b_best, 1), "unit": "rows/s",
            "vs_baseline": round(q3b_base / q3b_best, 3), "platform": platform,
        })

    # -- TPC-H Q1 (headline; LAST so a single-line parse of the tail sees it) --
    q1_best, q1_d, q1_c = _bench_query_d(s, QUERIES[1], runs)
    q1_base = min(pandas_q1(data)[0] for _ in range(runs))
    results.append({
        "metric": f"tpch_q1_sf{(big_sf if big_sf > 0 else sf):g}"
                  f"_rows_per_sec_per_chip",
        "value": round((len(data['lineitem']['l_orderkey'])) / q1_best, 1),
        "unit": "rows/s",
        "vs_baseline": round(q1_base / q1_best, 3), "platform": platform,
        "dispatches_per_exec": q1_d, "compile": q1_c,
        "profile": _profile_summary(s, QUERIES[1]),
    })

    # statement-summary snapshot: per-digest aggregates of everything this
    # bench run executed, so future runs can diff per-digest latency across
    # PRs (meta/statement_summary.py)
    ss = getattr(inst, "stmt_summary", None)
    if ss is not None:
        results.append({"metric": "statement_summary_snapshot",
                        "unit": "digests", "platform": platform,
                        "value": len(ss.rows()),
                        "statements": ss.top_digests(10)})

    results.insert(0, kernel_microbench(data, platform, runs))
    results.insert(1, dispatch_microbench(runs))

    for out in results:
        print(json.dumps(out))


def overload_bench(inst, s, data, platform):
    """Overload driver (PR 12 admission-control plane): closed-loop TP point
    serving measured alone, then again with a concurrent AP flood hammering
    a heavy aggregation while admission limits bite.  Reports TP QPS/p99
    with and without the flood, AP goodput, and the typed shed rate — the
    numbers that show the box degrading instead of collapsing."""
    import threading
    from galaxysql_tpu.utils import errors as _errors

    okeys = data["orders"]["o_orderkey"]
    keys = [int(k) for k in okeys[:: max(1, len(okeys) // 2048)]]
    tpl = "select o_totalprice from orders where o_orderkey = %d"
    ap_q = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) "
            "from lineitem group by l_orderkey order by 2 desc limit 10")
    s.execute(tpl % keys[0])  # register + warm the PointPlan
    s.execute(ap_q)           # warm the AP plan + classify the digest
    n_tp = int(os.environ.get("BENCH_OVERLOAD_TP_SESSIONS", "32"))
    per = int(os.environ.get("BENCH_OVERLOAD_PER_SESSION", "40"))
    n_ap = int(os.environ.get("BENCH_OVERLOAD_AP_THREADS", "8"))
    inst.config.set_instance("ADMISSION_AP_LIMIT", 2)
    inst.config.set_instance("ADMISSION_QUEUE_SIZE", 1)
    inst.config.set_instance("ADMISSION_WAIT_MS", 100)
    inst.admission._limit.clear()

    qps0, p99_0, errs = _closed_loop_point(inst, tpl, keys, n_tp, per)
    if errs:
        raise errs[0]

    stop = threading.Event()
    counts = {"ok": 0, "shed": 0, "other": 0}
    lock = threading.Lock()

    def flood():
        sx = Session(inst, schema="tpch")
        while not stop.is_set():
            try:
                sx.execute(ap_q)
                with lock:
                    counts["ok"] += 1
            except (_errors.ServerOverloadError, _errors.CclRejectError):
                with lock:
                    counts["shed"] += 1
                time.sleep(0.001)
            except Exception:
                with lock:
                    counts["other"] += 1
        sx.close()

    floods = [threading.Thread(target=flood, daemon=True)
              for _ in range(n_ap)]
    for t in floods:
        t.start()
    time.sleep(0.3)  # flood established before the measured TP pass
    qps1, p99_1, errs = _closed_loop_point(inst, tpl, keys, n_tp, per)
    stop.set()
    for t in floods:
        t.join(timeout=60)
    if errs:
        raise errs[0]
    total_ap = counts["ok"] + counts["shed"] + counts["other"]
    return [{
        "metric": f"tp_point_qps_under_ap_flood_{n_tp}_sessions",
        "value": round(qps1, 1), "unit": "qps",
        "vs_baseline": round(qps1 / max(qps0, 1e-9), 3),
        "p99_ms": round(p99_1, 3),
        "no_flood_qps": round(qps0, 1),
        "no_flood_p99_ms": round(p99_0, 3),
        "ap_flood_threads": n_ap,
        "ap_completed": counts["ok"],
        "ap_shed_typed": counts["shed"],
        "ap_untyped_failures": counts["other"],
        "ap_shed_rate": round(counts["shed"] / max(total_ap, 1), 3),
        "platform": platform,
    }]


def rebalance_bench(inst, s, platform):
    """`bench.py --rebalance-only` (make bench-rebalance): point serving
    measured quiesced, then DURING a live SPLIT PARTITION job — the
    rebalance-while-serving QPS dip and p99 inflation the elasticity plane
    promises to bound, plus the data-movement throughput itself.

    The split is slowed to bench scale (small chunks) so the measured
    closed-loop window genuinely overlaps the backfill+catchup+cutover
    pipeline rather than sampling an already-finished job."""
    import threading
    from galaxysql_tpu.ddl import rebalance as rb

    n_rows = int(os.environ.get("BENCH_REBALANCE_ROWS", "200000"))
    n_sessions = int(os.environ.get("BENCH_REBALANCE_SESSIONS", "32"))
    s.execute("CREATE DATABASE IF NOT EXISTS rbench")
    s.execute("USE rbench")
    s.execute("CREATE TABLE rt (id BIGINT PRIMARY KEY, grp BIGINT, "
              "v BIGINT) PARTITION BY HASH(id) PARTITIONS 4")
    store = inst.store("rbench", "rt")
    store.insert_pylists(
        {"id": list(range(n_rows)), "grp": [i % 97 for i in range(n_rows)],
         "v": list(range(n_rows))}, inst.tso.next_timestamp())
    tpl = "select v from rt where id = %d"
    keys = list(range(0, n_rows, max(1, n_rows // 4096)))
    nkeys = len(keys)
    s.execute(tpl % keys[0])  # register + warm the PointPlan
    s.execute(tpl % keys[0])

    def _loop(n, per):
        return _closed_loop_ops(
            inst, "rbench", n, per,
            lambda sx, i, j: sx.execute(tpl % keys[(i * 31 + j * 7) % nkeys]))

    _loop(n_sessions, 4)  # ramp
    per = max(4, int(os.environ.get("BENCH_REBALANCE_PER_SESSION", "24")))
    qps0, p99_0, errs0 = _loop(n_sessions, per)

    old_chunk = rb.RebalanceBackfillTask.CHUNK
    rb.RebalanceBackfillTask.CHUNK = max(
        256, n_rows // (4 * 64))  # ~64 checkpointed chunks per partition
    job_wall = [0.0]
    job_err: list = []

    def _run_split():
        sx = Session(inst, schema="rbench")
        t0 = time.perf_counter()
        try:
            sx.execute("ALTER TABLE rt SPLIT PARTITION p1 INTO 2")
        except Exception as e:  # pragma: no cover - surfaced in the json
            job_err.append(repr(e))
        finally:
            job_wall[0] = time.perf_counter() - t0
            sx.close()

    mover = threading.Thread(target=_run_split)
    mover.start()
    lats_qps = []
    try:
        # keep the closed loop running until the job finishes so the
        # measurement covers backfill, catchup, AND the fenced cutover
        while mover.is_alive():
            lats_qps.append(_loop(n_sessions, per))
    finally:
        mover.join()
        rb.RebalanceBackfillTask.CHUNK = old_chunk
    if not lats_qps:
        # split finished before the first overlap window (tiny table / fast
        # box): report the quiesced numbers as a degenerate 1.0x overlap
        lats_qps = [(qps0, p99_0, [])]
    qps1 = min(q for q, _, _ in lats_qps)
    p99_1 = max(p for _, p, _ in lats_qps)
    errs1 = sum(len(e) for _, _, e in lats_qps)
    moved = sum(p.num_rows for p in store.partitions[1:2]) + \
        store.partitions[-1].num_rows
    return [{
        "metric": "rebalance_while_serving_qps_per_chip",
        "value": round(qps1, 1), "unit": "qps",
        "vs_baseline": round(qps1 / max(qps0, 1e-9), 3),
        "quiesced_qps": round(qps0, 1),
        "quiesced_p99_ms": round(p99_0, 3),
        "during_p99_ms": round(p99_1, 3),
        "p99_inflation": round(p99_1 / max(p99_0, 1e-9), 2),
        "sessions": n_sessions,
        "rebalance_wall_s": round(job_wall[0], 2),
        "rows_moved": int(moved),
        "move_rows_per_sec": round(moved / max(job_wall[0], 1e-9), 1),
        "job_errors": job_err, "serve_errors": len(errs0) + errs1,
        "windows_during": len(lats_qps),
        "platform": platform,
    }]


def rebalance_only_main():
    """`bench.py --rebalance-only` (make bench-rebalance): fresh instance,
    no TPC-H load needed — the driver builds its own serving table."""
    inst = Instance()
    s = Session(inst)
    for out in rebalance_bench(inst, s, jax.devices()[0].platform):
        print(json.dumps(out))


def overload_only_main():
    """`bench.py --overload-only` (make bench-overload): TP serving under an
    AP flood with admission control engaged, on a small TPC-H load."""
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    inst, s, data = load(sf)
    for out in overload_bench(inst, s, data, jax.devices()[0].platform):
        print(json.dumps(out))


def batch_only_main():
    """`bench.py --batch-only` (make batch-smoke): just the closed-loop
    multi-session serving bench, on a small TPC-H load."""
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    inst, s, data = load(sf)
    for out in batch_serving_bench(inst, s, data, jax.devices()[0].platform):
        print(json.dumps(out))


def kernels_bench(platform: str):
    """Kernel tier: Pallas join/agg formulations vs the reference ones
    (direct steady-state kernel calls), plus the persistent AOT compile
    cache measured as a cold-vs-warm restart of the same query.  On CPU the
    Pallas kernels run in INTERPRET mode (the TPU compiled path has no chip
    to answer here) — reported as pallas_mode so the number is honest."""
    import tempfile

    import jax.numpy as jnp

    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.kernels import relational as R

    runs = max(int(os.environ.get("BENCH_RUNS", "3")), 3)
    pallas_mode = "compiled" if jax.default_backend() == "tpu" \
        else "interpret"

    def best_of(fn):
        fn()  # compile
        best = None
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            el = time.perf_counter() - t0
            best = el if best is None or el < best else best
        return best

    # -- grouped aggregation ------------------------------------------------
    n = 1 << 17
    rng = np.random.default_rng(42)
    g = jnp.asarray(rng.integers(0, 1024, n).astype(np.int64))
    v = jnp.asarray(rng.integers(0, 1000, n).astype(np.int64))
    live = jnp.ones(n, bool)
    specs = [R.AggSpec("sum", 0), R.AggSpec("count_star", -1)]

    def gb(mode):
        def run():
            with R.kernel_scope(mode):
                return R.hash_groupby([(g, None)], [(v, None)], specs, live,
                                      2048)
        return run

    agg = {label: n / best_of(gb(mode))
           for mode, label in (("off", "reference"), ("pallas", "pallas"))}
    yield {"metric": "kernel_groupby_rows_per_sec_per_chip",
           "value": round(agg["pallas"], 1), "unit": "rows/s",
           "vs_baseline": round(agg["pallas"] / agg["reference"], 3),
           "reference_rows_per_sec": round(agg["reference"], 1),
           "pallas_mode": pallas_mode, "rows": n, "platform": platform}

    # -- hash join ----------------------------------------------------------
    nb, npr = 1 << 15, 1 << 17
    bk = jnp.asarray(rng.integers(0, 1 << 14, nb).astype(np.int64))
    pk = jnp.asarray(rng.integers(0, 1 << 14, npr).astype(np.int64))
    b_live = jnp.ones(nb, bool)
    p_live = jnp.ones(npr, bool)
    cap = 1 << 19

    def jn(mode):
        def run():
            with R.kernel_scope(mode):
                return R.hash_join_pairs([(bk, None)], [(pk, None)], b_live,
                                         p_live, cap)
        return run

    join = {label: npr / best_of(jn(mode))
            for mode, label in (("off", "reference"), ("pallas", "pallas"))}
    yield {"metric": "kernel_join_probe_rows_per_sec_per_chip",
           "value": round(join["pallas"], 1), "unit": "rows/s",
           "vs_baseline": round(join["pallas"] / join["reference"], 3),
           "reference_rows_per_sec": round(join["reference"], 1),
           "pallas_mode": pallas_mode, "build_rows": nb, "probe_rows": npr,
           "platform": platform}

    # -- persistent AOT compile cache: cold vs warm restart -----------------
    def fresh_process():
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.clear()
        jax.clear_caches()
        ops.reset_compile_stats()

    d = os.path.join(tempfile.mkdtemp(prefix="gx_bench_cc_"), "db")
    q = "SELECT g, SUM(v), COUNT(*) FROM t GROUP BY g ORDER BY g"
    fresh_process()
    inst = Instance(data_dir=d)
    s = Session(inst)
    s.execute("CREATE DATABASE cc; USE cc")
    s.execute("CREATE TABLE t (g BIGINT, v BIGINT) "
              "PARTITION BY HASH(g) PARTITIONS 4")
    inst.store("cc", "t").insert_arrays(
        {"g": rng.integers(0, 64, 1 << 16).astype(np.int64),
         "v": rng.integers(0, 1000, 1 << 16).astype(np.int64)},
        inst.tso.next_timestamp())
    ops.reset_compile_stats()
    s.execute(q)
    cold_ms = ops.COMPILE_STATS["compile_ms"]
    cold_retraces = ops.COMPILE_STATS["retraces"]
    s.execute(q)  # steady: everything the next process should replay
    inst.save()
    s.close()

    fresh_process()
    inst2 = Instance(data_dir=d)
    s2 = Session(inst2)
    s2.execute("USE cc")
    s2.execute(q)
    warm_ms = ops.COMPILE_STATS["compile_ms"]
    hits = ops.COMPILE_STATS["cache_hits"]
    retr = ops.COMPILE_STATS["retraces"]
    s2.close()
    yield {"metric": "compile_cache_restart_compile_ms_speedup",
           "value": round(cold_ms / max(warm_ms, 1e-9), 1), "unit": "x",
           "cold_compile_ms": round(cold_ms, 1),
           "warm_compile_ms": round(warm_ms, 1),
           "cold_retraces": cold_retraces,
           "retraces_after_restart": retr,
           "cache_hits_after_restart": hits,
           "replay_fraction": round(hits / max(1, hits + retr), 3),
           "platform": platform}


def kernels_only_main():
    """`bench.py --kernels-only` (make bench-kernels): the kernel-tier
    microbench + the AOT compile-cache restart comparison (no TPC-H load)."""
    for out in kernels_bench(jax.devices()[0].platform):
        print(json.dumps(out))


def dml_only_main():
    """`bench.py --dml-only` (make bench-dml): the closed-loop DML + mixed
    read/write serving bench on a fresh instance (no TPC-H load needed —
    the driver builds its own write table)."""
    inst = Instance()
    s = Session(inst)
    for out in dml_serving_bench(inst, s, jax.devices()[0].platform):
        print(json.dumps(out))


def slo_bench(inst, s, data, platform):
    """SLO plane (PR 17): two numbers.  `slo_snapshot` reads the measured
    steady state BACK through the metric history — history-derived qps and
    the per-class recent p99 the burn-rate windows judge, plus every
    objective's state — proving the windows see what the bench measured.
    `slo_sampler_overhead` is the honest cost claim: closed-loop TP point
    serving with the history/SLO tick exercised around every pass vs
    hatched off entirely (sampling is off the query path by construction,
    so the target is <= 3% — noise, not a tax)."""
    okeys = data["orders"]["o_orderkey"]
    keys = [int(k) for k in okeys[:: max(1, len(okeys) // 2048)]]
    tpl = "select o_totalprice from orders where o_orderkey = %d"
    s.execute(tpl % keys[0])  # register + warm the PointPlan
    n_s = int(os.environ.get("BENCH_SLO_SESSIONS", "16"))
    per = int(os.environ.get("BENCH_SLO_PER_SESSION", "60"))
    reps = int(os.environ.get("BENCH_SLO_RUNS", "3"))
    _closed_loop_point(inst, tpl, keys, n_s, 4)  # ramp

    def best_pass(history_on):
        inst.config.set_instance("ENABLE_METRIC_HISTORY",
                                 1 if history_on else 0)
        best_qps, best_p99 = 0.0, 0.0
        for _ in range(reps):
            if history_on:
                inst.slo_tick(force=True)
            qps, p99, errs = _closed_loop_point(inst, tpl, keys, n_s, per)
            if history_on:
                inst.slo_tick(force=True)
            if errs:
                raise errs[0]
            if qps > best_qps:
                best_qps, best_p99 = qps, p99
        return best_qps, best_p99

    qps_on, p99_on = best_pass(True)
    qps_off, p99_off = best_pass(False)
    inst.config.set_instance("ENABLE_METRIC_HISTORY", 1)

    # pure sampler cost: a full registry+admission+summary snapshot, timed
    t0 = time.perf_counter()
    n_samp = 50
    for _ in range(n_samp):
        inst.metric_history.sample()
        inst.slo.evaluate()
    sample_ms = (time.perf_counter() - t0) * 1000.0 / n_samp

    mh = inst.metric_history
    snapshot = {
        "metric": "slo_snapshot", "platform": platform,
        "history_qps": round(mh.rate("queries_total"), 1),
        "recent_tp_p99_ms": round(
            mh.latest("stmt_class_tp_recent_p99_ms") or 0.0, 3),
        "error_rate_per_s": round(mh.rate("query_errors"), 6),
        "samples": int(mh.summary()["samples"]),
        "sample_plus_evaluate_ms": round(sample_ms, 3),
        "objectives": {r[0]: r[8] for r in inst.slo.rows()},
        "burning": inst.slo.burning_names(),
    }
    overhead_pct = round((qps_off - qps_on) / qps_off * 100.0, 2) \
        if qps_off > 0 else 0.0
    overhead = {
        "metric": "slo_sampler_overhead", "platform": platform,
        "sessions": n_s, "per_session": per, "runs": reps,
        "qps_on": round(qps_on, 1), "p99_on_ms": round(p99_on, 3),
        "qps_off": round(qps_off, 1), "p99_off_ms": round(p99_off, 3),
        "overhead_pct": overhead_pct, "target_pct": 3.0,
    }
    return [snapshot, overhead]


def slo_only_main():
    """`bench.py --slo-only` (make bench-slo): the SLO-plane snapshot +
    sampler-overhead bench on a small TPC-H load."""
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    inst, s, data = load(sf)
    for out in slo_bench(inst, s, data, jax.devices()[0].platform):
        print(json.dumps(out))


def tracing_bench(inst, s, data, platform):
    """Always-on tail-sampled tracing (ISSUE 20): the honest overhead
    claim.  Closed-loop TP point serving on the 32-session batched-serving
    loop with always-on collection at the DEFAULT head-sample rate (every
    query builds its span skeleton + phase ramp timestamps; the sampler's
    per-query cost is one dict probe + one compare) vs ENABLE_QUERY_TRACING
    off entirely.  Target <= 3%: collection is host-side perf_counter reads
    only — no device syncs, no extra dispatches (asserted here, not
    assumed), steady-state retraces 0."""
    from galaxysql_tpu.exec import operators as _ops

    okeys = data["orders"]["o_orderkey"]
    keys = [int(k) for k in okeys[:: max(1, len(okeys) // 2048)]]
    tpl = "select o_totalprice from orders where o_orderkey = %d"
    s.execute(tpl % keys[0])  # register + warm the PointPlan
    n_s = int(os.environ.get("BENCH_TRACING_SESSIONS", "32"))
    per = int(os.environ.get("BENCH_TRACING_PER_SESSION", "60"))
    reps = int(os.environ.get("BENCH_TRACING_RUNS", "3"))
    _closed_loop_point(inst, tpl, keys, n_s, 4)  # ramp both code paths

    def best_pass(tracing_on):
        inst.config.set_instance("ENABLE_QUERY_TRACING",
                                 1 if tracing_on else 0)
        _closed_loop_point(inst, tpl, keys, n_s, 4)  # re-warm under config
        best_qps, best_p99 = 0.0, 0.0
        for _ in range(reps):
            qps, p99, errs = _closed_loop_point(inst, tpl, keys, n_s, per)
            if errs:
                raise errs[0]
            if qps > best_qps:
                best_qps, best_p99 = qps, p99
        return best_qps, best_p99

    # hot-path guard measured inline: dispatch counts per pass must be
    # IDENTICAL on vs off, and a warm loop compiles nothing new
    inst.config.set_instance("ENABLE_QUERY_TRACING", 1)
    _closed_loop_point(inst, tpl, keys, n_s, 4)
    _ops.reset_dispatch_stats()
    r0 = _ops.COMPILE_STATS["retraces"]
    _closed_loop_point(inst, tpl, keys, n_s, 8)
    d_on = _ops.DISPATCH_STATS["dispatches"]
    retraces_on = _ops.COMPILE_STATS["retraces"] - r0
    inst.config.set_instance("ENABLE_QUERY_TRACING", 0)
    _closed_loop_point(inst, tpl, keys, n_s, 4)
    _ops.reset_dispatch_stats()
    _closed_loop_point(inst, tpl, keys, n_s, 8)
    d_off = _ops.DISPATCH_STATS["dispatches"]

    qps_on, p99_on = best_pass(True)
    qps_off, p99_off = best_pass(False)
    inst.config.set_instance("ENABLE_QUERY_TRACING", 1)
    overhead_pct = round((qps_off - qps_on) / qps_off * 100.0, 2) \
        if qps_off > 0 else 0.0
    st = inst.trace_store.stats()
    return [{
        "metric": "tracing_always_on_overhead", "platform": platform,
        "sessions": n_s, "per_session": per, "runs": reps,
        "qps_on": round(qps_on, 1), "p99_on_ms": round(p99_on, 3),
        "qps_off": round(qps_off, 1), "p99_off_ms": round(p99_off, 3),
        "overhead_pct": overhead_pct, "target_pct": 3.0,
        "dispatches_on": d_on, "dispatches_off": d_off,
        "dispatches_equal": d_on == d_off,
        "retraces_steady": retraces_on,
        "sample_rate": st["rate"],
        "store_count": st["count"], "store_bytes": st["bytes"],
        "store_budget": st["budget"],
    }]


def tracing_only_main():
    """`bench.py --tracing-only` (make bench-tracing): the always-on
    tracing overhead proof on a small TPC-H load; commits BENCH_r14.json."""
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    inst, s, data = load(sf)
    results = list(tracing_bench(inst, s, data, jax.devices()[0].platform))
    for out in results:
        print(json.dumps(out))
    envelope = {"n": 14, "cmd": "python bench.py --tracing-only", "rc": 0,
                "tail": json.dumps(results[-1]), "parsed": results}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_r14.json")
    with open(path, "w") as f:
        json.dump(envelope, f, indent=1)
        f.write("\n")


def htap_bench(platform):
    """`bench.py --htap-only` (make bench-htap): the columnar HTAP replica
    (PR 18) measured as its actual claim — scan-heavy AP queries on the
    CDC-fed columnar tier vs the SAME queries on the row store, BOTH under
    one sustained DML stream mutating lineitem (the row store re-derives
    visibility + lane concat per version bump; the replica serves immutable
    pre-encoded stripes at its watermark).  Then the stream stops, the
    tailer drains, and a quiesced phase asserts bit-identical results at
    the drained watermark plus zero steady-state retraces.  The freshness
    lag of every replica is sampled throughout — the SLA the router
    enforces must stay bounded while the writer hammers."""
    import threading

    from galaxysql_tpu.exec import operators as _ops

    sf = float(os.environ.get("BENCH_HTAP_SF",
                              os.environ.get("BENCH_SF", "0.2")))
    runs = int(os.environ.get("BENCH_RUNS", "3"))
    inst, s, data = load(sf)
    n_rows = len(data["lineitem"]["l_orderkey"])
    inst.config.set_instance("ENABLE_COLUMNAR_REPLICA", 1)
    inst.config.set_instance("COLUMNAR_POLL_MS", 20)
    inst.config.set_instance("COLUMNAR_WATERMARK_LAG_MS", 20)
    # cluster the fact table on ship date: Q6/Q3's date sargs then prune
    # whole stripes via the zone maps instead of filtering every row
    inst.config.set_instance("COLUMNAR_CLUSTER_BY", "lineitem:l_shipdate")
    mgr = inst.columnar
    seed_t0 = time.perf_counter()
    for t in tpch.TABLE_ORDER:
        mgr.ensure_ready("tpch", t, timeout_s=300.0)
    seed_wall = time.perf_counter() - seed_t0

    qids = [int(x) for x in
            os.environ.get("BENCH_HTAP_QUERIES", "1,6,3,5").split(",") if x]
    on_q = {q: "/*+TDDL:COLUMNAR(ON)*/ " + QUERIES[q] for q in qids}
    off_q = {q: "/*+TDDL:COLUMNAR(OFF)*/ " + QUERIES[q] for q in qids}
    # dedicated reader session: it never writes, so the read-your-writes
    # fence stays open and routing is decided purely by the watermark
    sr = Session(inst, schema="tpch")
    for q in qids:  # compile warmup for both paths, outside any timing
        sr.execute(off_q[q])
        routed0 = mgr.routed.value
        sr.execute(on_q[q])
        if mgr.routed.value == routed0:
            raise RuntimeError(f"COLUMNAR(ON) Q{q} did not route to the "
                               "replica — bench preconditions broken")

    # -- sustained DML stream + freshness-lag sampler -------------------------
    okeys = data["orders"]["o_orderkey"]
    wkeys = [int(k) for k in okeys[:: max(1, len(okeys) // 2048)]]
    upd = ("UPDATE lineitem SET l_suppkey = l_suppkey + 1 "
           "WHERE l_orderkey = %d")
    # prime the delete path: the first delete event the tailer sees builds
    # the pk map (one-time, proportional to table size); pay it here so the
    # measured lag window reflects steady-state tailing, not the build
    sp = Session(inst, schema="tpch")
    sp.execute(upd % wkeys[0])
    sp.close()
    ts_p = inst.tso.next_timestamp()
    deadline = time.time() + 120.0
    while any(rep.watermark < ts_p for rep in mgr.replicas.values()):
        mgr.tail_once()
        if time.time() > deadline:
            raise RuntimeError("pk-prime drain did not complete")
        time.sleep(0.02)
    stop = threading.Event()
    dml_n = [0]
    lags: list = []

    def writer():
        sw = Session(inst, schema="tpch")
        i = 0
        while not stop.is_set():
            sw.execute(upd % wkeys[i % len(wkeys)])
            dml_n[0] += 1
            i += 1
        sw.close()

    def sampler():
        while not stop.is_set():
            cur = max((rep.lag_ms() for rep in mgr.replicas.values()),
                      default=0.0)
            if cur >= 0:
                lags.append(cur)
            time.sleep(0.05)

    threads = [threading.Thread(target=writer, daemon=True),
               threading.Thread(target=sampler, daemon=True)]
    dml_t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(1.0)  # stream + tailer established before the timed passes

    results = []
    timings = {}
    for q in qids:
        off_best = min(_timed_exec(sr, off_q[q]) for _ in range(runs))
        routed0 = mgr.routed.value
        on_best = min(_timed_exec(sr, on_q[q]) for _ in range(runs))
        timings[q] = (on_best, off_best, mgr.routed.value - routed0)

    stop.set()
    for t in threads:
        t.join(timeout=60)
    dml_wall = time.perf_counter() - dml_t0

    # -- quiesce: drain the tailer past the last write, then assert identity --
    ts_q = inst.tso.next_timestamp()
    deadline = time.time() + 120.0
    while any(rep.watermark < ts_q for rep in mgr.replicas.values()):
        mgr.tail_once()
        if time.time() > deadline:
            raise RuntimeError("tailer failed to drain past the DML stream")
        time.sleep(0.02)
    equal = {}
    for q in qids:
        on_rows = sr.execute(on_q[q]).rows
        off_rows = sr.execute(off_q[q]).rows
        equal[q] = on_rows == off_rows
        if not equal[q]:
            raise RuntimeError(f"quiesced Q{q}: columnar result diverged "
                               "from the row store")
    for q in qids:  # steady-state warmup at the drained watermark
        sr.execute(on_q[q])
    _ops.reset_compile_stats()
    for q in qids:
        sr.execute(on_q[q])
    retraces = _ops.COMPILE_STATS["retraces"]

    lags.sort()
    for q in qids:
        on_best, off_best, routed = timings[q]
        results.append({
            "metric": f"htap_q{q}_sf{sf:g}_columnar_rows_per_sec_per_chip",
            "value": round(n_rows / on_best, 1), "unit": "rows/s",
            "vs_baseline": round(off_best / on_best, 3),
            "row_store_rows_per_sec": round(n_rows / off_best, 1),
            "routed_executions": routed,
            "quiesced_equal": equal[q],
            "platform": platform,
        })
    results.append({
        "metric": f"htap_freshness_lag_sf{sf:g}",
        "value": round(lags[len(lags) // 2], 1) if lags else -1.0,
        "unit": "ms",
        "vs_baseline": round(
            (lags[-1] if lags else 0.0) /
            float(inst.config.get("COLUMNAR_MAX_LAG_MS") or 10_000), 3),
        "lag_p95_ms": round(lags[int(len(lags) * 0.95)], 1) if lags else -1.0,
        "lag_max_ms": round(lags[-1], 1) if lags else -1.0,
        "lag_samples": len(lags),
        "dml_statements": dml_n[0],
        "dml_statements_per_sec": round(dml_n[0] / dml_wall, 1),
        "seed_wall_s": round(seed_wall, 2),
        "retraces_steady": retraces,
        "platform": platform,
    })
    sr.close()
    return results


def _timed_exec(s, q):
    t0 = time.perf_counter()
    s.execute(q)
    return time.perf_counter() - t0


def htap_only_main():
    """`bench.py --htap-only` (make bench-htap): run the columnar-vs-row
    HTAP bench and commit it to BENCH_r13.json."""
    results = htap_bench(jax.devices()[0].platform)
    for out in results:
        print(json.dumps(out), flush=True)
    envelope = {"n": 13, "cmd": "python bench.py --htap-only", "rc": 0,
                "tail": json.dumps(results[-1]), "parsed": results}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_r13.json")
    with open(path, "w") as f:
        json.dump(envelope, f, indent=1)
        f.write("\n")


def _spawn_coordinator(data_dir):
    """One coordinator subprocess over the shared metadb; returns
    (popen, mysql_port, sync_port) after the SERVER_READY handshake."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "galaxysql_tpu.net.server", "--port", "0",
         "--sync-port", "0", "--data-dir", data_dir, "--platform", "cpu",
         "--announce"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    line = p.stdout.readline()
    if not line.startswith("SERVER_READY"):
        p.kill()
        raise RuntimeError(f"coordinator failed to boot: {line!r}")
    _, mysql_port, sync_port = line.split()
    return p, int(mysql_port), int(sync_port)


def _scaleout_level(data_dir, n_coord, n_tables, sessions_per_peer,
                    per_session, ramp_ops):
    """One point on the curve: N coordinator subprocesses behind a front
    router, closed-loop point SELECTs spread by digest affinity."""
    import threading

    from galaxysql_tpu.server.instance import Instance
    from galaxysql_tpu.server.router import FrontRouter, RouterSession

    procs = [_spawn_coordinator(data_dir) for _ in range(n_coord)]
    hub = Instance(boot=False)  # front-of-tier process: routes, never serves
    router = FrontRouter(hub)
    router.local.down_until = float("inf")  # hub serves nothing itself
    try:
        for _p, mysql_port, sync_port in procs:
            router.add_remote("127.0.0.1", mysql_port, sync_port)

        # session -> table assignment BALANCED per peer: each peer serves
        # `sessions_per_peer` sessions over the tables the ring hands it,
        # so the curve measures tier capacity, not sha1 luck
        shapes = [f"select v from pt{t} where k = %d"
                  for t in range(n_tables)]
        by_peer = {}
        for t, tpl in enumerate(shapes):
            peer = router.targets_for(
                _scaleout_digest(tpl, "sb"), tpl % 1, "sb")[0]
            by_peer.setdefault(peer.node_id, []).append(tpl)
        plans = []  # one template per session
        for node_id, tpls in by_peer.items():
            for i in range(sessions_per_peer):
                plans.append(tpls[i % len(tpls)])
        uncovered = n_coord - len(by_peer)

        lat_lock = threading.Lock()
        lats, errors_seen = [], []

        def run(idx, tpl, n_ops, record):
            sess = RouterSession(router, schema="sb")
            try:
                for j in range(n_ops):
                    t0 = time.perf_counter()
                    sess.execute(tpl % (1 + (idx * 7 + j) % 64))
                    dt_ms = (time.perf_counter() - t0) * 1000.0
                    if record:
                        with lat_lock:
                            lats.append(dt_ms)
            except Exception as e:  # surfaced, never swallowed
                errors_seen.append(e)
            finally:
                sess.close()

        def pass_over(n_ops, record):
            ts = [threading.Thread(target=run, args=(i, tpl, n_ops, record))
                  for i, tpl in enumerate(plans)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return time.perf_counter() - t0

        pass_over(ramp_ops, record=False)  # warm plan caches + compiles
        routed0, hits0 = router.m_routed.value, router.m_hits.value
        retr0 = {p.node_id: p.sync_action("health", {}).get("retraces", 0)
                 for p in router.peers.values() if p is not router.local}
        wall = pass_over(per_session, record=True)
        if errors_seen:
            raise errors_seen[0]
        retr1 = {p.node_id: p.sync_action("health", {}).get("retraces", 0)
                 for p in router.peers.values() if p is not router.local}
        router.gossip_tick()
        routed = router.m_routed.value - routed0
        hits = router.m_hits.value - hits0
        lats.sort()
        return {
            "coordinators": n_coord,
            "sessions": len(plans),
            "qps": round(len(lats) / wall, 1),
            "p99_ms": round(lats[int(len(lats) * 0.99) - 1], 3),
            "p50_ms": round(lats[len(lats) // 2], 3),
            "affinity_hit_rate": round(hits / routed, 4) if routed else 1.0,
            "gossip_staleness_ms": round(router.staleness_ms(), 1),
            "steady_retraces": sum(retr1[n] - retr0[n] for n in retr1),
            "uncovered_peers": uncovered,
        }
    finally:
        router.close()
        for p, _, _ in procs:
            p.kill()
        for p, _, _ in procs:
            p.wait()


def _scaleout_digest(tpl, schema):
    from galaxysql_tpu.meta.statement_summary import digest_key
    from galaxysql_tpu.sql.parameterize import parameterize
    return digest_key(schema, parameterize(tpl % 1).cache_key)


def scaleout_bench():
    """`bench.py --scaleout-only` (make bench-scaleout): the serving-tier
    curve.  1/2/4 coordinator subprocesses over ONE shared metadb file,
    closed-loop point SELECTs through the front router with digest
    affinity; offered load scales with the tier (sessions-per-peer fixed).

    The workload is window-paced: a fixed BATCH_WINDOW_US pins the PR 6
    batch collection window, so each coordinator's ceiling is its batch
    cadence x in-flight sessions — a genuine per-process serialization
    point that scale-out removes.  (On this container `os.cpu_count()`
    cores; a CPU-saturated curve cannot show process scaling on one core,
    so the regime and core count ride the JSON for honesty.)"""
    import tempfile

    from galaxysql_tpu.server.instance import Instance
    from galaxysql_tpu.server.session import Session

    n_tables = int(os.environ.get("BENCH_SCALEOUT_TABLES", "16"))
    spp = int(os.environ.get("BENCH_SCALEOUT_SESSIONS_PER_PEER", "8"))
    per = int(os.environ.get("BENCH_SCALEOUT_PER_SESSION", "40"))
    ramp = int(os.environ.get("BENCH_SCALEOUT_RAMP", "6"))
    window_us = int(os.environ.get("BENCH_SCALEOUT_WINDOW_US", "60000"))
    levels = [int(x) for x in
              os.environ.get("BENCH_SCALEOUT_LEVELS", "1,2,4").split(",")]

    data_dir = tempfile.mkdtemp(prefix="scaleout_")
    seed = Instance(data_dir=data_dir)
    s = Session(seed)
    s.execute("CREATE DATABASE sb")
    s.execute("USE sb")
    for t in range(n_tables):
        s.execute(f"CREATE TABLE pt{t} (k BIGINT PRIMARY KEY, v BIGINT)")
        rows = ",".join(f"({k}, {k * 10})" for k in range(1, 65))
        s.execute(f"INSERT INTO pt{t} VALUES {rows}")
    # fixed batch window: the per-coordinator pacing the curve scales out
    # (persisted in the shared metadb -> every peer boots with it)
    s.execute(f"SET GLOBAL BATCH_WINDOW_US = {window_us}")
    seed.save()
    s.close()

    results = []
    for n in levels:
        out = _scaleout_level(data_dir, n, n_tables, spp, per, ramp)
        out.update({"metric": "scaleout_point_qps", "platform": "cpu",
                    "batch_window_us": window_us,
                    "cores": os.cpu_count()})
        if results:
            out["vs_baseline"] = round(out["qps"] / results[0]["qps"], 2)
            out["p99_vs_baseline"] = round(
                out["p99_ms"] / results[0]["p99_ms"], 2)
        results.append(out)
        print(json.dumps(out), flush=True)
    return results


def scaleout_only_main():
    """`bench.py --scaleout-only` (make bench-scaleout): run the serving
    tier curve and commit it to BENCH_r12.json."""
    results = scaleout_bench()
    envelope = {"n": 12, "cmd": "python bench.py --scaleout-only", "rc": 0,
                "tail": json.dumps(results[-1]), "parsed": results}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_r12.json")
    with open(path, "w") as f:
        json.dump(envelope, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    if "--batch-only" in sys.argv:
        batch_only_main()
    elif "--dml-only" in sys.argv:
        dml_only_main()
    elif "--skew-only" in sys.argv:
        skew_only_main()
    elif "--overload-only" in sys.argv:
        overload_only_main()
    elif "--rebalance-only" in sys.argv:
        rebalance_only_main()
    elif "--kernels-only" in sys.argv:
        kernels_only_main()
    elif "--slo-only" in sys.argv:
        slo_only_main()
    elif "--tracing-only" in sys.argv:
        tracing_only_main()
    elif "--scaleout-only" in sys.argv:
        scaleout_only_main()
    elif "--htap-only" in sys.argv:
        htap_only_main()
    else:
        main()
