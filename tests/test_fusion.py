"""Pipeline segment fusion: fused-vs-unfused equivalence, the global_jit LRU,
bucket_capacity ladder boundaries, and segment tracing spans.

The `fusion`-marked tests are the fast smoke target (`make fusion-smoke`):
TPC-H Q1/Q3 (+ Q5, SSB Q1.1, TPC-DS Q7) at tiny SF through BOTH execution
paths, asserting identical results — the tier-1 correctness guard for the
fuser."""

import math

import numpy as np
import pytest

import jax.numpy as jnp

from galaxysql_tpu.chunk.batch import Column, ColumnBatch, batch_from_pydict
from galaxysql_tpu.exec import fusion
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.fusion import (FusedPipelineOp, FusedSegment,
                                       collapse_streaming_chain)
from galaxysql_tpu.exec.operators import (AggCall, FilterOp, HashAggOp,
                                          ProjectOp, SourceOp, bucket_capacity,
                                          run_to_batch)
from galaxysql_tpu.expr import ir
from galaxysql_tpu.types import datatype as dt


def col(batch, name):
    c = batch.columns[name]
    return ir.ColRef(name, c.dtype, c.dictionary)


def sample_batch(n=200, device=False):
    schema = {"a": dt.BIGINT, "b": dt.DOUBLE, "s": dt.VARCHAR}
    data = {"a": list(range(n)),
            "b": [round(i * 0.25, 2) for i in range(n)],
            "s": ["x" if i % 2 else "y" for i in range(n)]}
    b = batch_from_pydict(data, schema)
    if device:
        cols = {k: Column(jnp.asarray(c.np_data()),
                          None if c.valid is None else jnp.asarray(c.np_valid()),
                          c.dtype, c.dictionary) for k, c in b.columns.items()}
        b = ColumnBatch(cols, None)
    return b


def seg_filter_project(b, lim=100):
    pred = ir.call("lt", col(b, "a"), ir.lit(lim))
    projs = [("c", ir.call("mul", col(b, "b"), ir.lit(2.0))),
             ("a", col(b, "a")), ("s", col(b, "s"))]
    return pred, projs


class TestGlobalJitLru:
    def test_lru_eviction_no_full_clear(self, monkeypatch):
        monkeypatch.setattr(ops, "_JIT_CACHE_LIMIT", 4)
        with ops._JIT_CACHE_LOCK:
            saved = dict(ops._JIT_CACHE)
            ops._JIT_CACHE.clear()
        try:
            for i in range(4):
                ops.global_jit(("lru", i), lambda i=i: f"f{i}")
            # hit entry 0: it becomes most-recent
            assert ops.global_jit(("lru", 0), lambda: "REBUILT") == "f0"
            # overflow: evicts the OLDEST (entry 1), not the whole cache
            ops.global_jit(("lru", 4), lambda: "f4")
            assert len(ops._JIT_CACHE) == 4  # no thundering full clear
            assert ("lru", 1) not in ops._JIT_CACHE
            for k in (("lru", 0), ("lru", 2), ("lru", 3), ("lru", 4)):
                assert k in ops._JIT_CACHE
            # the hit entry survives and does NOT rebuild
            assert ops.global_jit(("lru", 0), lambda: "REBUILT") == "f0"
        finally:
            with ops._JIT_CACHE_LOCK:
                ops._JIT_CACHE.clear()
                ops._JIT_CACHE.update(saved)

    def test_built_flag_fires_only_on_build(self):
        calls = []
        key = ("lru-flag", object())  # unique key
        ops.global_jit(key, lambda: 1, built_flag=lambda: calls.append(1))
        ops.global_jit(key, lambda: 2, built_flag=lambda: calls.append(1))
        assert calls == [1]


class TestBucketCapacityLadder:
    def test_quarter_step_boundaries_above_64k(self):
        K64, K80, K96, K112, K128 = (1 << 16, 80 << 10, 96 << 10,
                                     112 << 10, 1 << 17)
        assert bucket_capacity(K64) == K64
        assert bucket_capacity(K64 + 1) == K80
        assert bucket_capacity(K80) == K80
        assert bucket_capacity(K80 + 1) == K96
        assert bucket_capacity(K96) == K96
        assert bucket_capacity(K96 + 1) == K112
        assert bucket_capacity(K112) == K112
        assert bucket_capacity(K112 + 1) == K128
        assert bucket_capacity(K128) == K128

    def test_exact_powers_of_two(self):
        for p in (10, 14, 16, 17, 18, 20):
            assert bucket_capacity(1 << p) == 1 << p

    def test_below_64k_powers_of_two(self):
        assert bucket_capacity(1) == 1024
        assert bucket_capacity(1025) == 2048
        assert bucket_capacity(40000) == 1 << 16

    def test_quarter_ladder_bounds_padding_waste(self):
        for n in (70000, 100000, 150000, 1_200_000):
            cap = bucket_capacity(n)
            assert cap >= n
            assert cap / n <= 1.26  # ladder caps padding waste at ~25%

    def test_fused_and_unfused_pick_identical_buckets(self):
        # a bucket-padded scan batch flows through both paths shape-preserving:
        # fused and unfused executions see identical capacities end to end
        raw = sample_batch(300, device=True)
        b = raw.pad_to(bucket_capacity(raw.capacity))
        assert b.capacity == bucket_capacity(300) == 1024
        pred, projs = seg_filter_project(b)
        u_out = list(ProjectOp(FilterOp(SourceOp([b]), pred), projs).batches())
        f_out = list(FusedPipelineOp(SourceOp([b]),
                                     FusedSegment([("filter", pred),
                                                   ("project", projs)])).batches())
        assert [o.capacity for o in u_out] == [o.capacity for o in f_out] \
            == [1024]
        u = run_to_batch(ProjectOp(FilterOp(SourceOp([b]), pred), projs))
        f = run_to_batch(FusedPipelineOp(SourceOp([b]),
                                         FusedSegment([("filter", pred),
                                                       ("project", projs)])))
        assert u.capacity == f.capacity


class TestFusedSegment:
    def test_fused_matches_unfused_chain(self):
        for device in (False, True):
            b = sample_batch(200, device=device)
            pred, projs = seg_filter_project(b)
            u = run_to_batch(ProjectOp(FilterOp(SourceOp([b]), pred), projs))
            f = run_to_batch(FusedPipelineOp(
                SourceOp([b]),
                FusedSegment([("filter", pred), ("project", projs)])))
            assert sorted(u.to_pylist()) == sorted(f.to_pylist())

    def test_passthrough_columns_zero_copy(self):
        b = sample_batch(200, device=True)
        pred, projs = seg_filter_project(b)
        seg = FusedSegment([("filter", pred), ("project", projs)])
        out = seg.run_batch(b)
        # untouched lanes are the ORIGINAL buffers, not XLA output copies
        assert out.columns["a"].data is b.columns["a"].data
        assert out.columns["s"].data is b.columns["s"].data
        assert "c" in seg.computed and "a" not in seg.computed

    def test_filter_only_segment_returns_mask_only(self):
        b = sample_batch(200, device=True)
        pred = ir.call("lt", col(b, "a"), ir.lit(42))
        seg = FusedSegment([("filter", pred)])
        out = seg.run_batch(b)
        assert out.num_live() == 42
        for name in b.columns:
            assert out.columns[name].data is b.columns[name].data

    def test_lifted_literals_share_one_program(self):
        b = sample_batch(200, device=True)
        with ops._JIT_CACHE_LOCK:
            before = set(ops._JIT_CACHE)
        keys = set()
        for lim in (10, 50, 120):
            pred, projs = seg_filter_project(b, lim=lim)
            seg = FusedSegment([("filter", pred), ("project", projs)])
            keys.add(seg.key())
            run_to_batch(FusedPipelineOp(SourceOp([b]), seg))
        assert len(keys) == 1  # value-independent: one cache entry, no retrace
        with ops._JIT_CACHE_LOCK:
            added = set(ops._JIT_CACHE) - before
        assert len(added) <= 1

    def test_rename_chain_stays_passthrough(self):
        b = sample_batch(100)
        st1 = ("project", [("x", col(b, "a")), ("b", col(b, "b"))])
        st2 = ("project", [("y", ir.ColRef("x", dt.BIGINT, None)),
                           ("z", ir.call("add", ir.ColRef("x", dt.BIGINT, None),
                                         ir.lit(1)))])
        seg = FusedSegment([st1, st2])
        assert seg.alias["y"] == "a"   # rename-of-rename resolves to the input
        assert seg.alias["z"] is None  # computed
        out = seg.run_batch(b)
        assert out.columns["y"].data is b.columns["a"].data
        np.testing.assert_array_equal(np.asarray(out.columns["z"].data),
                                      np.arange(100) + 1)

    def test_agg_prelude_matches_stacked_operators(self):
        b = sample_batch(400, device=True)
        pred, projs = seg_filter_project(b, lim=300)
        groups = [("s", ir.ColRef("s", dt.VARCHAR, b.columns["s"].dictionary))]
        aggs = [AggCall("sum", ir.ColRef("c", dt.DOUBLE, None), "sc"),
                AggCall("count_star", None, "n")]
        u = run_to_batch(HashAggOp(
            ProjectOp(FilterOp(SourceOp([b]), pred), projs), groups, aggs))
        seg = FusedSegment([("filter", pred), ("project", projs)])
        f = run_to_batch(HashAggOp(SourceOp([b]), groups, aggs, prelude=seg))
        ur = sorted(u.compact().to_pylist())
        fr = sorted(f.compact().to_pylist())
        assert len(ur) == len(fr)
        for ru, rf in zip(ur, fr):
            assert ru[0] == rf[0] and ru[2] == rf[2]
            assert math.isclose(ru[1], rf[1], rel_tol=1e-9)

    def test_collapse_streaming_chain(self):
        from galaxysql_tpu.plan import logical as L
        scan = L.Values([], [])
        pred = ir.call("lt", ir.ColRef("a", dt.BIGINT, None), ir.lit(5))
        node = L.Project(L.Filter(scan, pred),
                         [("a", ir.ColRef("a", dt.BIGINT, None))])
        stages, base = collapse_streaming_chain(node)
        assert [k for k, _ in stages] == ["filter", "project"]
        assert base is scan

    def test_dispatch_counter_counts_fusion_win(self):
        b = sample_batch(200, device=True)
        pred, projs = seg_filter_project(b)
        ops.reset_dispatch_stats()
        run_to_batch(ProjectOp(FilterOp(SourceOp([b]), pred), projs))
        unfused = ops.DISPATCH_STATS["dispatches"]
        ops.reset_dispatch_stats()
        run_to_batch(FusedPipelineOp(
            SourceOp([b]), FusedSegment([("filter", pred), ("project", projs)])))
        fused = ops.DISPATCH_STATS["dispatches"]
        assert (unfused, fused) == (2, 1)


class TestJoinProbePrelude:
    def _sides(self, device=True):
        n = 500
        probe = sample_batch(n, device=device)
        bschema = {"k": dt.BIGINT, "v": dt.DOUBLE}
        bdata = {"k": [i * 3 for i in range(60)],
                 "v": [float(i) for i in range(60)]}
        build = batch_from_pydict(bdata, bschema)
        if device:
            cols = {k: Column(jnp.asarray(c.np_data()), None, c.dtype, None)
                    for k, c in build.columns.items()}
            build = ColumnBatch(cols, None)
        bk = [ir.ColRef("k", dt.BIGINT, None)]
        pk = [ir.ColRef("a", dt.BIGINT, None)]
        pred = ir.call("lt", ir.ColRef("a", dt.BIGINT, None), ir.lit(200))
        return build, probe, bk, pk, pred

    def _check(self, monkeypatch=None, native=True, spill=1 << 62):
        from galaxysql_tpu.exec.operators import HashJoinOp
        build, probe, bk, pk, pred = self._sides()
        if not native:
            from galaxysql_tpu import native as native_mod
            monkeypatch.setattr(native_mod, "AVAILABLE", False)
        u = run_to_batch(HashJoinOp(
            SourceOp([build]), FilterOp(SourceOp([probe]), pred), bk, pk,
            "inner", spill_threshold=spill)).compact()
        seg = FusedSegment([("filter", pred)])
        f = run_to_batch(HashJoinOp(
            SourceOp([build]), SourceOp([probe]), bk, pk, "inner",
            spill_threshold=spill, probe_prelude=seg)).compact()
        assert sorted(u.to_pylist()) == sorted(f.to_pylist())
        assert u.num_live() > 0  # the join actually matched rows

    def test_native_path_matches(self):
        self._check()

    def test_device_path_matches(self, monkeypatch):
        self._check(monkeypatch, native=False)

    def test_grace_spill_path_matches(self, monkeypatch):
        self._check(monkeypatch, native=False, spill=1)

    def test_probe_prelude_saves_the_filter_dispatch(self, monkeypatch):
        from galaxysql_tpu import native as native_mod
        from galaxysql_tpu.exec.operators import HashJoinOp
        monkeypatch.setattr(native_mod, "AVAILABLE", False)
        build, probe, bk, pk, pred = self._sides()
        ops.reset_dispatch_stats()
        run_to_batch(HashJoinOp(SourceOp([build]),
                                FilterOp(SourceOp([probe]), pred), bk, pk,
                                "inner"))
        unfused = ops.DISPATCH_STATS["dispatches"]
        ops.reset_dispatch_stats()
        run_to_batch(HashJoinOp(SourceOp([build]), SourceOp([probe]), bk, pk,
                                "inner",
                                probe_prelude=FusedSegment([("filter", pred)])))
        fused = ops.DISPATCH_STATS["dispatches"]
        assert (unfused, fused) == (1, 0)  # the probe-side filter fused away

    def test_non_inner_joins_reject_prelude(self):
        from galaxysql_tpu.exec.operators import HashJoinOp
        build, probe, bk, pk, pred = self._sides(device=False)
        with pytest.raises(AssertionError):
            HashJoinOp(SourceOp([build]), SourceOp([probe]), bk, pk, "left",
                       probe_prelude=FusedSegment([("filter", pred)]))


class TestSegmentTracing:
    def test_spans_record_chain_rows_and_compile_state(self):
        from galaxysql_tpu.utils.tracing import SEGMENT_TRACER
        b = sample_batch(200, device=True)
        pred, projs = seg_filter_project(b, lim=77)
        seg = FusedSegment([("filter", pred), ("project", projs)])
        with SEGMENT_TRACER.scoped() as spans:
            seg.run_batch(b)
            seg.run_batch(b)
        assert len(spans) == 2
        s0, s1 = spans
        assert s0.chain == "filter>project"
        assert s0.segment_id == seg.segment_id == s1.segment_id
        assert s0.rows_in == 200 and s0.rows_out == 77
        assert not s1.compiled  # second dispatch is a cache hit
        assert s1.wall_ms >= 0


# -- SQL-level fused-vs-unfused smoke (the `fusion` marker target) ------------


def _rows_close(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(sorted(a), sorted(b)):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                assert math.isclose(float(va), float(vb),
                                    rel_tol=1e-9, abs_tol=1e-9)
            else:
                assert va == vb


def _run_both(s, sql, monkeypatch):
    r_f = s.execute(sql)
    monkeypatch.setattr(fusion, "ENABLED", False)
    try:
        r_u = s.execute(sql)
    finally:
        monkeypatch.setattr(fusion, "ENABLED", True)
    _rows_close(r_f.rows, r_u.rows)
    return r_f


@pytest.fixture(scope="module")
def tpch_session():
    from galaxysql_tpu.server.instance import Instance
    from galaxysql_tpu.server.session import Session
    from galaxysql_tpu.storage import tpch
    data = tpch.generate(0.01)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_pylists(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    yield s
    s.close()


@pytest.mark.fusion
class TestTpchFusedVsUnfused:
    def test_q1(self, tpch_session, monkeypatch):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        r = _run_both(tpch_session, QUERIES[1], monkeypatch)
        assert len(r.rows) == 4

    def test_q3(self, tpch_session, monkeypatch):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        _run_both(tpch_session, QUERIES[3], monkeypatch)

    def test_q5(self, tpch_session, monkeypatch):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        _run_both(tpch_session, QUERIES[5], monkeypatch)

    def test_fusion_engages_and_no_fuse_hint_disables(self, tpch_session):
        s = tpch_session
        q = ("select l_returnflag, sum(l_quantity) from lineitem "
             "where l_shipdate <= date '1998-09-02' group by l_returnflag")
        s.execute(q)
        assert any("fuse" in t for t in s.last_trace)
        s.execute("/*+TDDL: NO_FUSE*/ " + q)
        assert not any("fuse" in t for t in s.last_trace)


@pytest.mark.fusion
@pytest.mark.slow  # two extra engine instances + datasets; covered by `make fusion-smoke`
class TestSsbTpcdsFusedVsUnfused:
    @pytest.fixture(scope="class")
    def ssb_session(self):
        from galaxysql_tpu.server.instance import Instance
        from galaxysql_tpu.server.session import Session
        from galaxysql_tpu.storage import ssb
        data = ssb.generate(0.01)
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE ssb")
        s.execute("USE ssb")
        for t in ssb.TABLE_ORDER:
            s.execute(ssb.SSB_DDL[t])
            inst.store("ssb", t).insert_arrays(data[t],
                                               inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE " + ", ".join(ssb.TABLE_ORDER))
        yield s
        s.close()

    @pytest.fixture(scope="class")
    def tpcds_session(self):
        from galaxysql_tpu.server.instance import Instance
        from galaxysql_tpu.server.session import Session
        from galaxysql_tpu.storage import tpcds
        data = tpcds.generate(0.005)
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE tpcds")
        s.execute("USE tpcds")
        for t in tpcds.TABLE_ORDER:
            s.execute(tpcds.TPCDS_DDL[t])
            inst.store("tpcds", t).insert_pylists(data[t],
                                                  inst.tso.next_timestamp())
        s.execute("ANALYZE TABLE " + ", ".join(tpcds.TABLE_ORDER))
        yield s
        s.close()

    def test_ssb_q1_1(self, ssb_session, monkeypatch):
        from galaxysql_tpu.storage import ssb
        _run_both(ssb_session, ssb.QUERIES["1.1"], monkeypatch)

    def test_tpcds_q7(self, tpcds_session, monkeypatch):
        from galaxysql_tpu.storage import tpcds
        _run_both(tpcds_session, tpcds.QUERIES["q7"], monkeypatch)


@pytest.mark.fusion
@pytest.mark.slow  # compiles MPP shard programs; covered by `make fusion-smoke`
class TestMppFusedVsUnfused:
    def test_mpp_chain_and_agg_prelude(self, tpch_session):
        import jax
        from galaxysql_tpu.parallel.mpp import MppExecutor
        from galaxysql_tpu.plan.physical import ExecContext
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        inst = tpch_session.instance
        mesh = inst.mesh()
        if mesh is None:
            pytest.skip("no multi-device mesh")
        for q in (QUERIES[6], QUERIES[1]):
            plan = inst.planner.plan_select(q, "tpch")
            ctx_f = ExecContext(inst.stores)
            out_f = MppExecutor(ctx_f, mesh).execute(plan.rel)
            ctx_u = ExecContext(inst.stores)
            ctx_u.enable_fusion = False
            out_u = MppExecutor(ctx_u, mesh).execute(plan.rel)
            _rows_close(out_f.to_pylist(), out_u.to_pylist())
            assert any("fuse" in t for t in ctx_f.trace)
            assert not any("fuse" in t for t in ctx_u.trace)
