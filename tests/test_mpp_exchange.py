"""The exchange plane on four virtual devices: TPC-H Q3 through `MppExecutor`
against a plain pandas reference down each exchange kind and through an
overflow retry, the compaction of a join's sides to their live rows,
`EXCHANGE_STATS` against bytes, calls and slots worked out by hand, the
`stage:Join` span attributes in SHOW TRACE, and the traced run's host
transfers."""

import re

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galaxysql_tpu.chunk.batch import Column
from galaxysql_tpu.exec.operators import bucket_capacity
from galaxysql_tpu.parallel import exchange
from galaxysql_tpu.parallel import mpp as M
from galaxysql_tpu.parallel.mesh import make_mesh, shard_bucket
from galaxysql_tpu.plan import logical as L
from galaxysql_tpu.plan.physical import ExecContext
from galaxysql_tpu.plan.rules import estimate_rows
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import tracing

S = 4
# the scale at which every side of both of Q3's joins is sparse enough to be
# compacted: `customer` 1,875 rows a shard in 2,048 slots, a fifth BUILDING
SF, SEED = 0.05, 2147483659
EPOCH = np.datetime64("1970-01-01")


@pytest.fixture(scope="module")
def env():
    assert len(jax.devices()) >= S, "conftest must provide virtual devices"
    data = tpch.generate(SF, seed=SEED)
    inst = Instance()
    inst._mesh = make_mesh(S)            # the session's mesh: four of the eight
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    yield inst, s, data
    s.close()


def q3_reference(data):
    """Q3 (BUILDING, 1995-03-15) in pandas, revenue in exact scaled integers:
    [(orderkey, revenue x 10^4, orderdate days, shippriority)], the ten first
    by revenue descending, then date."""
    cutoff = int((np.datetime64("1995-03-15") - EPOCH).astype(int))
    c = pd.DataFrame({"ck": data["customer"]["c_custkey"],
                      "seg": data["customer"]["c_mktsegment"]})
    o = pd.DataFrame({"ok": data["orders"]["o_orderkey"],
                      "ck": data["orders"]["o_custkey"],
                      "od": np.asarray(data["orders"]["o_orderdate"], np.int64),
                      "sp": data["orders"]["o_shippriority"]})
    li = pd.DataFrame({
        "ok": data["lineitem"]["l_orderkey"],
        "price": np.round(np.asarray(data["lineitem"]["l_extendedprice"]) * 100
                          ).astype(np.int64),
        "disc": np.round(np.asarray(data["lineitem"]["l_discount"]) * 100
                         ).astype(np.int64),
        "ship": np.asarray(data["lineitem"]["l_shipdate"], np.int64)})
    j = li[li.ship > cutoff].merge(
        o[o.od < cutoff].merge(c[c.seg == "BUILDING"], on="ck"), on="ok")
    rev = (j.price * (100 - j.disc)).groupby([j.ok, j.od, j.sp]).sum()
    rows = sorted(((int(k[0]), int(v), int(k[1]), int(k[2]))
                   for k, v in rev.items()), key=lambda r: (-r[1], r[2]))
    return rows[:10]


def normalise(batch):
    out = []
    for ok, rev, od, sp in batch.to_pylist():
        days = int((np.datetime64(str(od)) - EPOCH).astype(int))
        out.append((int(ok), int(round(float(rev) * 10 ** 4)), days, int(sp)))
    return out


def joins_of(node):
    found = [node] if isinstance(node, L.Join) else []
    for child in node.children:
        found += joins_of(child)
    return found


def run_q3(inst, traced=False):
    inst.frag_cache.clear()     # a warm aggregate would replay and run no join
    plan = inst.planner.plan_select(QUERIES[3], "tpch")
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    ex = M.MppExecutor(ctx, make_mesh(S))
    tc = tracing.TraceContext(7, node="t") if traced else None
    with tracing.activate(tc):
        batch = ex.execute(plan.rel)
    return batch, ([] if tc is None else tc.spans)


def exchanges(spans):
    """The Join stages' exchange kinds, outermost join first."""
    return [sp.attrs["exchange"] for sp in spans
            if sp.kind == "stage" and sp.name == "mpp:Join"]


def second_build_estimates(inst):
    """(estimate of the first join's build side, of the second's): the limit
    between them sends the first down broadcast and the second down shuffle."""
    plan = inst.planner.plan_select(QUERIES[3], "tpch")
    outer, inner = joins_of(plan.rel)[:2]
    ests = []
    for j in (inner, outer):
        ests.append(min(estimate_rows(j.left), estimate_rows(j.right)))
    return ests


@pytest.fixture()
def limit(monkeypatch):
    def set_limit(value):
        monkeypatch.setattr(M, "BROADCAST_BUILD_LIMIT", value)
    return set_limit


def test_q3_planners_choice_equals_pandas(env):
    inst, _s, data = env
    batch, spans = run_q3(inst, traced=True)
    assert normalise(batch) == q3_reference(data)
    # at this scale both build sides are under the limit
    assert exchanges(spans) == ["broadcast", "broadcast"]


@pytest.mark.parametrize("kind", ["broadcast", "shuffle"])
def test_q3_second_join_down_each_exchange_equals_pandas(env, limit, kind):
    inst, _s, data = env
    first, second = second_build_estimates(inst)
    assert first < second
    limit(int(second) + 1 if kind == "broadcast" else int(first))
    batch, spans = run_q3(inst, traced=True)
    assert normalise(batch) == q3_reference(data)
    assert exchanges(spans) == [kind, "broadcast"]
    outer = next(sp for sp in spans if sp.name == "mpp:Join")
    assert outer.attrs["retries"] == 0 and 0 < outer.attrs["fill"] <= 1
    if kind == "shuffle":
        assert outer.attrs["build_rows"] > 0 and outer.attrs["probe_rows"] > 0
        assert outer.attrs["quota_b"] >= 128 and outer.attrs["cap"] >= 1024


def test_q3_shuffle_from_a_quota_that_overflows_retries_and_equals_pandas(
        env, limit, monkeypatch):
    inst, _s, data = env
    limit(int(second_build_estimates(inst)[0]))
    monkeypatch.setattr(M.MppExecutor, "_shuffle_quotas",
                        lambda self, bR, pR: (128, 128))
    before = dict(M.EXCHANGE_STATS)
    batch, spans = run_q3(inst, traced=True)
    assert normalise(batch) == q3_reference(data)
    outer = next(sp for sp in spans if sp.name == "mpp:Join")
    assert outer.attrs["exchange"] == "shuffle"
    assert outer.attrs["retries"] >= 1
    assert outer.attrs["quota_p"] > 128      # the ladder doubled it
    # over compacted sides: the ladder is the quotas', not the compaction's
    assert "compact_b" in outer.attrs and "compact_p" in outer.attrs
    assert M.EXCHANGE_STATS["overflow_retries"] - before["overflow_retries"] \
        == outer.attrs["retries"]


# -- a join's sides, compacted to their live rows ----------------------------------


def slots_in_out(attr):
    """`<slots in>/<slots out>:<how the kept slots were found>`"""
    slots, path = attr.split(":")
    slots_in, slots_out = map(int, slots.split("/"))
    assert path == exchange.compact_path(slots_in, slots_out)
    return slots_in, slots_out


def most_rows_a_shard(spans, stage):
    return max(sp.attrs["rows"] for sp in spans
               if sp.kind == "shard" and sp.parent_id == stage.span_id)


@pytest.mark.parametrize("kind", ["broadcast", "shuffle"])
def test_q3_joins_take_their_shapes_from_the_rows_that_are_live(env, limit,
                                                               kind):
    inst, _s, data = env
    first, second = second_build_estimates(inst)
    limit(int(second) + 1 if kind == "broadcast" else int(first))
    before = dict(M.EXCHANGE_STATS)
    batch, spans = run_q3(inst, traced=True)
    assert normalise(batch) == q3_reference(data)
    joins = [sp for sp in spans if sp.name == "mpp:Join"]
    assert exchanges(spans) == [kind, "broadcast"]
    seen_in = seen_out = 0
    for join in joins:
        sides = [sp for sp in spans
                 if sp.kind == "stage" and sp.parent_id == join.span_id]
        assert len(sides) == 2
        got = {}
        for name in ("compact_b", "compact_p"):      # engaged on both sides
            slots_in, slots_out = slots_in_out(join.attrs[name])
            assert 2 * slots_out <= slots_in
            got[name] = slots_out
            seen_in += S * slots_in
            seen_out += S * slots_out
        # R' is the bucket over the fullest shard of each side
        assert sorted(got.values()) == sorted(
            bucket_capacity(most_rows_a_shard(spans, side)) for side in sides)
        # and every shape downstream follows it, by the formulas that stood
        if join.attrs["exchange"] == "broadcast":
            assert join.attrs["build_slots"] == S * got["compact_b"]
            assert join.attrs["cap"] == bucket_capacity(
                max(2 * got["compact_p"], 1024))
        else:
            assert join.attrs["quota_b"] == max(2 * got["compact_b"] // S, 128)
            assert join.attrs["quota_p"] == max(2 * got["compact_p"] // S, 128)
            assert join.attrs["cap"] == bucket_capacity(
                max(2 * join.attrs["quota_p"] * S, 1024))
        assert join.attrs["retries"] == 0
    delta = {k: M.EXCHANGE_STATS[k] - before[k] for k in before}
    assert delta["compactions"] == 4
    assert delta["compact_slots_in"] == seen_in
    assert delta["compact_slots_out"] == seen_out


R_SLOTS = 4096      # slots a shard of the hand-made batches below


def live_mask(case):
    rng = np.random.default_rng(28)
    live = np.zeros((S, R_SLOTS), np.bool_)
    if case == "all_live":
        live[:] = True
    elif case == "one_shard_empty":
        live[:] = rng.random((S, R_SLOTS)) < 0.3
        live[2] = False
    elif case == "one_row_in_the_last_slot":
        live[S - 1, R_SLOTS - 1] = True
    elif case == "half_the_slots_to_the_last":
        # rows == n / 2 and shard 1 fills every one of them, its last live
        # row in the shard's last slot: the deepest the old search went
        live[:] = rng.random((S, R_SLOTS)) < 0.3
        live[1] = False
        live[1, rng.permutation(R_SLOTS - 1)[:R_SLOTS // 2 - 1]] = True
        live[1, R_SLOTS - 1] = True
    elif case.startswith("random_"):
        live[:] = rng.random((S, R_SLOTS)) < int(case[7:]) / 100
    else:
        assert case == "all_dead"
    return live.reshape(-1)


def hand_made(live, nullable):
    n = live.shape[0]
    rng = np.random.default_rng(7)
    valid = jax.numpy.asarray(rng.random(n) < 0.7) if nullable else None
    cols = {"k": Column(jax.numpy.arange(n, dtype=np.int64), None, dt.BIGINT),
            "v": Column(jax.numpy.asarray(rng.integers(0, 99, n)), valid,
                        dt.BIGINT)}
    return M.DistBatch(cols, jax.numpy.asarray(live), False)


def rows_by_shard(batch):
    """[(k, v or None), ...] of each shard's live rows, in slot order."""
    live = np.asarray(batch.live).reshape(S, -1)
    k = np.asarray(batch.columns["k"].data).reshape(S, -1)
    v = np.asarray(batch.columns["v"].data).reshape(S, -1)
    valid = batch.columns["v"].valid
    ok = np.ones_like(live) if valid is None else \
        np.asarray(valid).reshape(S, -1)
    return [[(int(a), int(b) if c else None)
             for a, b, c in zip(k[s][live[s]], v[s][live[s]], ok[s][live[s]])]
            for s in range(S)]


@pytest.mark.parametrize("nullable", [False, True], ids=["not_null", "nulls"])
@pytest.mark.parametrize("case", [
    "all_live", "all_dead", "one_shard_empty", "one_row_in_the_last_slot",
    "half_the_slots_to_the_last", "random_1", "random_50"])
def test_compaction_keeps_rows_order_and_nulls(case, nullable):
    live = live_mask(case)
    batch = hand_made(live, nullable)
    before = dict(M.EXCHANGE_STATS)
    out = M.MppExecutor(None, make_mesh(S))._compact(batch, False)
    delta = {k: M.EXCHANGE_STATS[k] - before[k] for k in before}
    fullest = int(live.reshape(S, -1).sum(axis=1).max())
    rows = bucket_capacity(fullest)
    if 2 * rows > R_SLOTS:
        # dense: passed through untouched, nothing counted
        assert out is batch and out.compacted is None
        assert not any(delta.values())
        assert case in ("all_live", "random_50")
        return
    assert out.compacted == (R_SLOTS, rows)
    assert out.live.shape == (S * rows,) and not out.replicated
    assert (out.columns["v"].valid is None) == (not nullable)
    assert rows_by_shard(out) == rows_by_shard(batch)
    assert list(out.shard_rows) == list(live.reshape(S, -1).sum(axis=1))
    # live rows first on every shard: the slots behind them are dead
    packed = np.asarray(out.live).reshape(S, rows)
    assert all(not packed[s, int(packed[s].sum()):].any() for s in range(S))
    # and they are dead in every lane: zeros, in a validity lane too
    for col in out.columns.values():
        for lane in (col.data, col.valid):
            if lane is not None:
                assert not np.asarray(lane).reshape(S, rows)[~packed].any()
    if case == "half_the_slots_to_the_last":
        assert rows == R_SLOTS // 2 and packed[1].all()
    assert delta["compactions"] == 1 and not delta["overflow_retries"]


@pytest.mark.parametrize("rows,path", [
    (1024, "search"), (2048, "scatter"), (512, "search"), (32768, "scatter")])
@pytest.mark.parametrize("nullable", [False, True], ids=["not_null", "nulls"])
def test_compact_rows_either_side_of_the_crossover(rows, path, nullable):
    """`compact_path` by hand: 65,536 slots kept in 1,024 (a 64th: searched
    for) and in 2,048 (scattered) are the same rows in the same order, the
    slots behind them dead and zero; the search is a loop in the lowered text
    and no scatter, the scatter no loop."""
    n = 65536
    assert exchange.compact_path(n, rows) == path
    rng = np.random.default_rng(36)
    live = np.zeros(n, np.bool_)
    live[rng.permutation(n - 1)[:500]] = True
    live[n - 1] = True                      # the last slot holds a live row
    lanes = [np.arange(1, n + 1, dtype=np.int64),
             rng.integers(1, 99, n).astype(np.int32)]
    if nullable:
        lanes.append(rng.random(n) < 0.5)
    fn = jax.jit(lambda la, lv: exchange.compact_rows(la, lv, rows))
    args = ([jax.numpy.asarray(x) for x in lanes], jax.numpy.asarray(live))
    out, keep = fn(*args)
    assert list(np.asarray(keep)) == [True] * 501 + [False] * (rows - 501)
    for x, y in zip(lanes, out):
        want = np.zeros(rows, x.dtype)
        want[:501] = x[live]
        assert (np.asarray(y) == want).all()
    text = fn.lower(*args).as_text()
    assert ("stablehlo.while" in text) == (path == "search")
    assert ("stablehlo.scatter" in text) == (path == "scatter")


@pytest.mark.parametrize("rows,case", [
    (64, "more_slots_than_there_are"), (20, "as_many_as_live"),
    (7, "fewer_than_live")])
def test_compact_rows_alone_keeps_the_first_rows_that_fit(rows, case):
    """What the hybrid join's `compact_hot` leans on: a quota under the live
    count keeps the first rows in slot order (its caller reads the flag and
    climbs), one over the slots there are is padded with dead slots."""
    rng = np.random.default_rng(5)
    n = 32
    live = np.zeros(n, np.bool_)
    live[rng.permutation(n)[:20]] = True
    k = np.arange(1, n + 1, dtype=np.int64)
    valid = rng.random(n) < 0.5
    (k_out, valid_out), keep = jax.jit(
        lambda la, lv: exchange.compact_rows(la, lv, rows))(
            [jax.numpy.asarray(k), jax.numpy.asarray(valid)],
            jax.numpy.asarray(live))
    kept = min(20, rows)
    assert list(np.asarray(keep)) == [True] * kept + [False] * (rows - kept)
    assert list(np.asarray(k_out)) == list(k[live][:kept]) + [0] * (rows - kept)
    assert list(np.asarray(valid_out)) == \
        list(valid[live][:kept]) + [False] * (rows - kept)


def test_exchange_stats_of_one_compaction_by_hand():
    # 100 rows a shard in 4,096 slots: the bucket over 100 is the smallest,
    # 1,024; then a batch with every slot live, which adds nothing
    live = np.zeros((S, R_SLOTS), np.bool_)
    live[:, ::41][:, :100] = True
    assert live.sum() == S * 100
    ex = M.MppExecutor(None, make_mesh(S))
    before = dict(M.EXCHANGE_STATS)
    out = ex._compact(hand_made(live.reshape(-1), False), True)
    ex._compact(hand_made(np.ones(S * R_SLOTS, np.bool_), False), True)
    delta = {k: M.EXCHANGE_STATS[k] - before[k] for k in before}
    assert out.compacted == (4096, 1024)
    assert delta.pop("compactions") == 1
    assert delta.pop("compact_slots_in") == S * 4096
    assert delta.pop("compact_slots_out") == S * 1024
    assert not any(delta.values())      # no exchange, no statement


# -- the repartition alone, against NumPy -------------------------------------------


R_PART = 512        # slots a shard of the repartitioned side


def destinations(case):
    """(dest [S, R] of every slot, live [S, R], quota) of a case."""
    rng = np.random.default_rng(36)
    dest = rng.integers(0, S, (S, R_PART))
    live = rng.random((S, R_PART)) < 0.8
    quota = 2 * R_PART // S
    if case == "one_destination":
        dest[:] = 2
        quota = R_PART
    elif case == "one_destination_empty":
        dest[dest == 1] = 3
    elif case == "all_dead":
        live[:] = False
    elif case in ("quota_met_exactly", "quota_one_short"):
        fullest = max(int((live[s] & (dest[s] == d)).sum())
                      for s in range(S) for d in range(S))
        quota = fullest - (case == "quota_one_short")
    else:
        assert case == "uniform"
    return dest, live, quota


def repartitioned(lanes, live, hashes, quota):
    """`exchange.repartition_by_hash` under `shard_map` on four devices:
    (lanes [S, S * quota], live [S, S * quota], overflow [S])."""

    def spmd(lanes, live, hashes):
        out, live_x, over = exchange.repartition_by_hash(lanes, live, hashes,
                                                         quota)
        return out, live_x, over.reshape(1)

    fn = jax.jit(M.shard_map(spmd, mesh=make_mesh(S), in_specs=P("shard"),
                           out_specs=P("shard"), check_vma=False))
    out, live_x, over = fn(lanes, live, hashes)
    return ([np.asarray(x).reshape(S, -1) for x in out],
            np.asarray(live_x).reshape(S, -1), np.asarray(over))


@pytest.mark.parametrize("nullable", [False, True], ids=["not_null", "nulls"])
@pytest.mark.parametrize("case", [
    "uniform", "one_destination", "one_destination_empty", "all_dead",
    "quota_met_exactly", "quota_one_short"])
def test_repartition_buckets_hold_their_rows_in_slot_order(case, nullable):
    dest, live, quota = destinations(case)
    rng = np.random.default_rng(8)
    # the destination is read from the hash's HIGH word; the low word is noise
    hashes = (dest.astype(np.uint64) + S * rng.integers(0, 1 << 20, dest.shape)
              .astype(np.uint64)) << np.uint64(32) | \
        rng.integers(0, 1 << 32, dest.shape).astype(np.uint64)
    k = np.arange(S * R_PART, dtype=np.int64).reshape(S, R_PART) + 1
    v = rng.integers(1, 99, (S, R_PART)).astype(np.int32)
    lanes = [k, v] + ([rng.random((S, R_PART)) < 0.7] if nullable else [])
    got, live_x, over = repartitioned(
        [jax.numpy.asarray(x.reshape(-1)) for x in lanes],
        jax.numpy.asarray(live.reshape(-1)),
        jax.numpy.asarray(hashes.reshape(-1)), quota)
    assert live_x.shape == (S, S * quota)
    for d in range(S):
        for s in range(S):
            sent = live[s] & (dest[s] == d)         # in source slot order
            kept = min(int(sent.sum()), quota)
            bucket = slice(s * quota, (s + 1) * quota)
            # the live slots of a bucket are its first `kept`, no other
            assert list(live_x[d, bucket]) == [True] * kept + \
                [False] * (quota - kept), (case, s, d)
            for lane, moved in zip(lanes, got):
                assert list(moved[d, bucket][:kept]) == \
                    list(lane[s][sent][:kept]), (case, s, d)
                assert not moved[d, bucket][kept:].any()    # dead rows nowhere
    counts = np.array([[int((live[s] & (dest[s] == d)).sum()) for d in range(S)]
                       for s in range(S)])
    assert list(over) == list((counts > quota).any(axis=1))
    assert over.any() == (case == "quota_one_short")
    if case == "quota_met_exactly":
        assert counts.max() == quota
    assert int(live_x.sum()) == int(np.minimum(counts, quota).sum())


def lowered_for_a_tpu(fn, *shapes):
    """`fn`'s per-shard body as the chip's compiler is handed it, under
    `shard_map` on four devices: lowered for a TPU here, no chip."""
    spmd = M.shard_map(fn, mesh=make_mesh(S), in_specs=P("shard"),
                     out_specs=P("shard"), check_vma=False)
    return jax.jit(spmd).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def compacted(lanes, live, _hashes):
    return exchange.compact_rows(lanes, live, 2048)


def dealt(lanes, live, hashes):
    out, live_x, over = exchange.repartition_by_hash(lanes, live, hashes, 2048)
    return out, live_x, over.reshape(1)


@pytest.mark.parametrize("body,scope", [
    (compacted, "exchange/compact"), (dealt, "exchange/repartition")],
    ids=["compact", "repartition"])
def test_a_scope_as_lowered_searches_and_sorts_nothing(body, scope):
    """Lowered for a TPU here, no chip: no loop (a search is one) and no sort
    anywhere in the program; every scatter writes 32-bit words (or flags) to
    32-bit indices.  `exchange/compact`: ONE scatter, of the slot ids, and a
    gather a lane.  `exchange/repartition`: no gather; a scatter a 32-bit
    lane, two for the 64-bit one, none for `live` (written from the counts);
    an `all_to_all` a lane and one for `live`; a running count a destination
    (each a call of the ONE `cumsum` in the text)."""
    from test_join_probe_ranges import _located
    lanes = [jax.ShapeDtypeStruct((S * 4096,), t)
             for t in (jnp.int64, jnp.int32, jnp.int32, jnp.bool_)]
    ops_ = list(_located(lowered_for_a_tpu(
        body, lanes, jax.ShapeDtypeStruct((S * 4096,), jnp.bool_),
        jax.ShapeDtypeStruct((S * 4096,), jnp.uint64))))
    inside = [(op, line) for op, line, where in ops_ if scope in where]
    names = [op for op, _ in inside]
    every = [op for op, _, _ in ops_]
    assert not {"stablehlo.while", "stablehlo.sort"} & set(every)
    assert every.count("stablehlo.reduce_window") == 1
    scatters = [re.search(r"\}\) : \(tensor<\d+x(\w+)>, tensor<\d+x1x(\w+)>", line)
                .groups() for op, line in inside if op == "stablehlo.scatter"]
    if scope == "exchange/compact":
        assert scatters == [("i32", "i32")]
        assert names.count("stablehlo.gather") == len(lanes)
        assert "stablehlo.all_to_all" not in names
    else:
        assert sorted(scatters) == sorted(
            [("ui32", "i32")] * 2 + [("i32", "i32")] * 2 + [("i1", "i32")])
        assert "stablehlo.gather" not in every
        assert names.count("stablehlo.all_to_all") == len(lanes) + 1


# -- EXCHANGE_STATS against a count by hand ----------------------------------------


@pytest.fixture(scope="module")
def tiny():
    """b(k, v) 400 rows and p(k, w) 3,000 rows, BIGINT NOT NULL lanes, four
    partitions each: one partition a shard.  Partitioned by the payload
    column: HASH(k) would line the rows up with the exchange's own hash of k
    and one destination would overflow its quota."""
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE ex; USE ex")
    for t, c, n in (("b", "v", 400), ("p", "w", 3000)):
        s.execute(f"CREATE TABLE {t} (k BIGINT NOT NULL, {c} BIGINT NOT NULL) "
                  f"PARTITION BY HASH({c}) PARTITIONS {S}")
        inst.store("ex", t).insert_arrays(
            {"k": np.arange(n, dtype=np.int64) % 400,
             c: np.arange(n, dtype=np.int64)}, inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE b, p")
    yield inst, s
    s.close()


def slots_per_shard(inst, table):
    store = inst.store("ex", table)
    per_shard = [0] * S
    for pid, part in enumerate(store.partitions):
        per_shard[pid % S] += part.num_rows
    return shard_bucket(max(per_shard))


def run_tiny(inst):
    inst.frag_cache.clear()
    plan = inst.planner.plan_select(
        "SELECT p.w, b.v FROM p JOIN b ON p.k = b.k", "ex")
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    before = dict(M.EXCHANGE_STATS)
    batch = M.MppExecutor(ctx, make_mesh(S)).execute(plan.rel)
    assert len(batch.to_pylist()) == 3000
    return {k: M.EXCHANGE_STATS[k] - before[k] for k in before}


def test_exchange_stats_of_one_repartition_by_hand(tiny, limit):
    inst, _s = tiny
    limit(0)
    got = run_tiny(inst)
    # each side: two int64 lanes and `live`, one all_to_all each; a shard's
    # send buffer is S x quota slots of 8 + 8 + 1 bytes
    qb = max(2 * slots_per_shard(inst, "b") // S, 128)
    qp = max(2 * slots_per_shard(inst, "p") // S, 128)
    assert got["statements"] == 1
    assert got["all_to_all_calls"] == 2 * 3
    assert got["all_to_all_bytes"] == S * qb * 17 + S * qp * 17
    assert got["all_gather_calls"] == 0 and got["all_gather_bytes"] == 0
    assert got["slots_offered"] == S * (S * qb + S * qp)
    assert got["live_rows"] == 400 + 3000 and got["overflow_retries"] == 0
    # every slot of both tables is live: neither side was compacted
    assert got["compactions"] == got["compact_slots_in"] == 0


def test_exchange_stats_of_one_broadcast_by_hand(tiny, limit):
    inst, _s = tiny
    limit(1 << 19)
    got = run_tiny(inst)
    # the build side's two lanes and `live`, one all_gather each; a shard's
    # gathered result is S x R slots, every row live on every shard
    R = slots_per_shard(inst, "b")
    assert got["all_gather_calls"] == 3 and got["all_to_all_calls"] == 0
    assert got["all_gather_bytes"] == S * R * 17
    assert got["slots_offered"] == S * S * R
    assert got["live_rows"] == S * 400
    assert got["compactions"] == got["compact_slots_out"] == 0


# -- spans in SHOW TRACE ------------------------------------------------------------


def test_show_trace_of_q3_shows_both_joins_with_quotas_cap_and_fill(env, limit):
    inst, s, _data = env
    limit(int(second_build_estimates(inst)[0]))
    s.vars["MPP_MIN_AP_ROWS"] = 1
    s.vars["ENABLE_QUERY_TRACING"] = True
    try:
        s.execute("/*+TDDL:FRAGMENT_CACHE(OFF)*/ " + QUERIES[3])
        lines = [r[0] for r in s.execute("SHOW TRACE").rows]
    finally:
        s.vars.pop("ENABLE_QUERY_TRACING", None)
        s.vars.pop("MPP_MIN_AP_ROWS", None)
    joins = [ln for ln in lines if "mpp:Join [stage]" in ln]
    assert len(joins) == 2, lines
    shuffle, broadcast = joins
    assert "exchange=shuffle" in shuffle and "exchange=broadcast" in broadcast
    for attr in ("quota_b=", "quota_p=", "cap=", "retries=0", "fill=",
                 "out_fill=", "rows=", "compact_b=", "compact_p="):
        assert attr in shuffle, (attr, shuffle)
    for attr in ("build_slots=", "cap=", "retries=0", "fill=", "rows=",
                 "compact_b=", "compact_p="):
        assert attr in broadcast, (attr, broadcast)
    # the Chrome export carries the same attributes
    prof = inst.profiles.entries()[-1]
    events = tracing.chrome_trace(prof.trace_id, prof.spans)["traceEvents"]
    assert {e["args"].get("exchange") for e in events
            if e.get("name") == "mpp:Join"} == {"shuffle", "broadcast"}


# -- the traced round is the timed path --------------------------------------------


class CountingNumpy:
    """`numpy` for `parallel/mpp.py` with `asarray` recording what it brings
    over from a device."""

    def __init__(self, sink):
        self.sink = sink

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **kw):
        if isinstance(x, jax.Array):
            self.sink.append(int(x.size))
        return np.asarray(x, *a, **kw)


def transfers_of_run(inst, monkeypatch, traced):
    """Sizes of the device arrays `MppExecutor.run` brought to the host for
    Q3's join subtree (below the aggregate: no result, no gather)."""
    sink = []
    real_get = jax.device_get
    inst.frag_cache.clear()

    def counting_get(tree):
        sink.extend(int(x.size) for x in jax.tree.leaves(tree)
                    if isinstance(x, jax.Array))
        return real_get(tree)

    plan = inst.planner.plan_select(QUERIES[3], "tpch")
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    ex = M.MppExecutor(ctx, make_mesh(S))
    with monkeypatch.context() as m:
        m.setattr(M, "np", CountingNumpy(sink))
        m.setattr(M.jax, "device_get", counting_get)
        tc = tracing.TraceContext(9, node="t") if traced else None
        with tracing.activate(tc):
            out = ex.run(joins_of(plan.rel)[0])
    assert not out.replicated
    return sorted(sink), ([] if tc is None else tc.spans)


def test_the_traced_run_brings_no_lane_longer_than_the_mesh_to_the_host(
        env, limit, monkeypatch):
    inst, _s, _data = env
    limit(int(second_build_estimates(inst)[0]))
    untraced, _ = transfers_of_run(inst, monkeypatch, traced=False)
    traced, spans = transfers_of_run(inst, monkeypatch, traced=True)
    extra = list(traced)
    for size in untraced:
        extra.remove(size)
    # what tracing adds: the stages' row counts, S integers (or one) a stage
    assert extra and max(extra) <= S, extra
    stages = [sp for sp in spans if sp.kind == "stage"]
    joins = [sp for sp in stages if sp.name == "mpp:Join"]
    # the two joins' counts came with their overflow flags, in both runs
    assert len(joins) == 2 and len(extra) == len(stages) - len(joins)
    assert all("rows" in sp.attrs for sp in stages)
    join = next(sp for sp in stages if sp.name == "mpp:Join")
    shards = [sp for sp in spans
              if sp.kind == "shard" and sp.parent_id == join.span_id]
    assert len(shards) == S
    assert sum(sp.attrs["rows"] for sp in shards) == join.attrs["rows"] > 0
