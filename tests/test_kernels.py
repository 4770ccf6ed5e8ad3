"""Kernels: one formulation per operator per platform, and the persistent AOT
compile cache.

Coverage: the formulations the chip runs (`sort_groupby`,
`_hash_join_pairs_sorted`) against the CPU's scatter formulations
(`hash_groupby`, `_hash_join_pairs_table`), and the hybrid join's union-lane
probe (one formulation on every backend) against the sorted join, as
RELATIONS, since the layouts differ by design: NULL keys, empty input,
duplicate keys, the overflow ladder's doubling, both skew orientations; TPC-H
Q1/Q3/Q5/Q6/Q9 end to end under either formulation; the persistent AOT cache's
restart round trip (save -> boot -> same query with zero steady retraces and
cache hits > 0), corrupted-entry recompile tolerance, and the compile_cache_*
observability surfaces.  Fast target: make kernel-smoke.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.compile_cache import GLOBAL_COMPILE_CACHE
from galaxysql_tpu.kernels import relational as R
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session

pytestmark = pytest.mark.kernel


def _lanes(pairs):
    return [(jnp.asarray(d), None if v is None else jnp.asarray(v))
            for d, v in pairs]


def _groups(r: R.GroupByResult):
    """{key tuple: agg tuple} over live slots, NULL as None: a group-by's
    result as a relation, whatever order and slots the formulation chose."""
    def column(lane):
        d, v = lane
        d = np.asarray(d).tolist()
        return d if v is None else \
            [x if ok else None for x, ok in zip(d, np.asarray(v).tolist())]
    slots = np.nonzero(np.asarray(r.live))[0].tolist()
    keys, aggs = [column(k) for k in r.keys], [column(a) for a in r.aggs]
    out = {tuple(k[i] for k in keys): tuple(a[i] for a in aggs) for i in slots}
    assert len(out) == len(slots) == int(r.num_groups)  # one slot a group
    return out


def _pairs(r: R.JoinPairs):
    """Verified (build row, probe row) pairs in order: a join's result as a
    relation.  A list, not a set: a pair enumerated twice must show."""
    live = np.asarray(r.live)
    return sorted(zip(np.asarray(r.build_idx)[live].tolist(),
                      np.asarray(r.probe_idx)[live].tolist()))


def _both_groupbys(keys, inputs, specs, live, max_groups, max_rounds=64):
    """(the CPU's scatter formulation: the oracle, the chip's sort one)."""
    keys, inputs, live = _lanes(keys), _lanes(inputs), jnp.asarray(live)
    specs = tuple(specs)  # one program each, as the operators build them

    def scatter(keys, inputs, live):
        return R.hash_groupby(keys, inputs, specs, live, max_groups, max_rounds)

    def sort(keys, inputs, live):
        return R.sort_groupby(keys, inputs, specs, live, max_groups)
    return (jax.jit(scatter)(keys, inputs, live),
            jax.jit(sort)(keys, inputs, live))


def _assert_same_join(oracle, subject):
    assert not bool(oracle.overflow) and not bool(subject.overflow)
    assert _pairs(oracle) == _pairs(subject)
    assert np.array_equal(np.asarray(oracle.probe_matched),
                          np.asarray(subject.probe_matched))


EVERY_KIND = [R.AggSpec("sum", 0), R.AggSpec("count", 0),
              R.AggSpec("count_star", -1), R.AggSpec("min", 0),
              R.AggSpec("max", 0)]


# -- the chip's formulations vs the CPU's: direct kernel matrix ---------------


class TestFormulationEquivalence:
    """The oracle is the scatter formulation tier-1 runs everywhere else; the
    subject is what `prefer_scatter()` false selects, which the benchmark's
    cells run and tier-1 otherwise reaches for whole queries only."""

    def test_groupby_duplicate_keys(self):
        rng = np.random.default_rng(7)
        n = 1536
        k = rng.integers(0, 53, n).astype(np.int64)  # heavy duplication
        v = rng.integers(-1000, 1000, n).astype(np.int64)
        specs = [R.AggSpec("sum", 0), R.AggSpec("count_star", -1),
                 R.AggSpec("min", 1)]
        ref, got = _both_groupbys([(k, None)], [(v, None), (k, None)], specs,
                                  np.ones(n, bool), 256)
        assert not bool(ref.overflow) and not bool(got.overflow)
        assert _groups(ref) == _groups(got) and len(_groups(ref)) == 53

    def test_groupby_null_keys(self):
        rng = np.random.default_rng(8)
        n = 1024
        k1 = rng.integers(0, 31, n).astype(np.int64)
        k2 = rng.integers(0, 5, n).astype(np.int64)
        valid1 = rng.random(n) > 0.2  # NULLs form their own groups
        v = rng.integers(0, 100, n).astype(np.int64)
        specs = [R.AggSpec("sum", 0), R.AggSpec("count_star", -1)]
        ref, got = _both_groupbys([(k1, valid1), (k2, None)], [(v, None)],
                                  specs, rng.random(n) > 0.1, 512)
        assert not bool(ref.overflow) and not bool(got.overflow)
        assert _groups(ref) == _groups(got)
        assert any(key[0] is None for key in _groups(got))

    def test_groupby_nullable_inputs_every_kind(self):
        rng = np.random.default_rng(7)
        n, ndv = 30_000, 2000
        k1 = rng.integers(-ndv // 2, ndv // 2, n)
        k1v = rng.random(n) > 0.1
        k2 = rng.integers(0, 7, n).astype(np.int32)
        x = rng.integers(-10**12, 10**12, n)
        xv = rng.random(n) > 0.2
        ref, got = _both_groupbys([(k1, k1v), (k2, None)], [(x, xv)],
                                  EVERY_KIND, rng.random(n) > 0.15, 20_000)
        assert not bool(ref.overflow) and not bool(got.overflow)
        assert _groups(ref) == _groups(got)

    @pytest.mark.parametrize("n,specs,max_groups", [
        (256, [R.AggSpec("sum", 0)], 64), (64, EVERY_KIND, 16)])
    def test_groupby_empty_input(self, n, specs, max_groups):
        # zero LIVE rows at positive static capacity — the engine's "empty"
        lane = [(np.zeros(n, np.int64), None)]
        ref, got = _both_groupbys(lane, lane, specs, np.zeros(n, bool),
                                  max_groups)
        assert not bool(ref.overflow) and not bool(got.overflow)
        assert _groups(ref) == _groups(got) == {}

    @pytest.mark.parametrize("n,small,max_rounds,doubled", [
        (512, 16, 8, 1024), (4096, 128, 64, 8192)])
    def test_groupby_overflow_ladder_doubling(self, n, small, max_rounds,
                                              doubled):
        """Overflow semantics ARE the ladder contract: both formulations must
        overflow at the same undersized capacity and both must succeed, with
        the same relation, after the ladder's doubling."""
        rng = np.random.default_rng(9)
        k = rng.permutation(n).astype(np.int64)  # n distinct groups
        lane = [(k, None)]
        specs = [R.AggSpec("count_star", -1)]
        live = np.ones(n, bool)
        ref, got = _both_groupbys(lane, lane, specs, live, small, max_rounds)
        assert bool(ref.overflow) and bool(got.overflow)
        ref, got = _both_groupbys(lane, lane, specs, live, doubled)
        assert not bool(ref.overflow) and not bool(got.overflow)
        assert _groups(ref) == _groups(got) and len(_groups(got)) == n

    @pytest.mark.parametrize("nb,npr,ndv,dead,cap", [
        (512, 1024, 37, 0.0, 16 * 1024), (2048, 20_000, 1500, 0.2, 1 << 18)])
    def test_join_pairs_duplicates_and_nulls(self, nb, npr, ndv, dead, cap):
        rng = np.random.default_rng(10)
        bk = rng.integers(0, ndv, nb).astype(np.int64)
        pk = rng.integers(0, ndv + 13, npr).astype(np.int64)
        bv = rng.random(nb) > 0.15  # NULL build keys never match
        pv = rng.random(npr) > 0.15
        args = (_lanes([(bk, bv)]), _lanes([(pk, pv)]),
                jnp.asarray(rng.random(nb) >= dead),
                jnp.asarray(rng.random(npr) >= dead), cap)
        ref = R._hash_join_pairs_table(*args)
        _assert_same_join(ref, R._hash_join_pairs_sorted(*args))
        pairs = _pairs(ref)  # duplicates on both sides did pair up
        assert len(pairs) > len({b for b, _ in pairs}) and \
            len(pairs) > len({p for _, p in pairs})

    @pytest.mark.parametrize("nb,npr,cap", [(128, 256, 256), (64, 256, 1024)])
    def test_join_empty_build(self, nb, npr, cap):
        args = (_lanes([(np.zeros(nb, np.int64), None)]),
                _lanes([(np.zeros(npr, np.int64), None)]),
                jnp.zeros(nb, bool), jnp.ones(npr, bool), cap)
        ref = R._hash_join_pairs_table(*args)
        _assert_same_join(ref, R._hash_join_pairs_sorted(*args))
        assert _pairs(ref) == [] and not np.asarray(ref.probe_matched).any()

    @pytest.mark.parametrize("orientation", ["skewed_probe", "skewed_build"])
    def test_hybrid_orientations(self, orientation):
        """The hybrid join's union-lane probe has one formulation on every
        backend (the slot-table CSR); the chip runs it beside the sorted
        join, so the two must enumerate the same pairs for BOTH skews."""
        rng = np.random.default_rng(11)
        if orientation == "skewed_probe":
            nb, npr, hot_side = 256, 2048, "p"
        else:
            nb, npr, hot_side = 2048, 256, "b"
        bk = rng.integers(0, 40, nb).astype(np.int64)
        pk = rng.integers(0, 40, npr).astype(np.int64)
        hot = bk if hot_side == "b" else pk
        hot[: len(hot) // 2] = 7  # one dominant key
        args = (_lanes([(bk, None)]), _lanes([(pk, None)]),
                jnp.ones(nb, bool), jnp.ones(npr, bool), 8 * max(nb, npr))
        _assert_same_join(R._hash_join_pairs_sorted(*args),
                          R.hash_join_probe_hybrid(*args))


# -- TPC-H end to end under either formulation --------------------------------

TPCH_QIDS = [1, 3, 5, 6, 9]  # the cells' four queries, and Q9
NO_FRAG = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "  # a replayed fragment runs nothing


@pytest.fixture(scope="module")
def tpch_session():
    from galaxysql_tpu.storage import tpch
    data = tpch.generate(0.005)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    yield s
    s.close()


@pytest.fixture(scope="module")
def cpu_formulation_rows(tpch_session):
    """Module-scoped, so set up before any test's `chip_formulation`."""
    from galaxysql_tpu.storage.tpch_queries import QUERIES
    return {q: tpch_session.execute(NO_FRAG + QUERIES[q]).rows
            for q in TPCH_QIDS}


class TestTpchFormulationEquivalence:
    @pytest.mark.parametrize("qid", TPCH_QIDS)
    def test_chip_formulation_equals_cpu(self, tpch_session,
                                         cpu_formulation_rows, qid,
                                         chip_formulation):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        got = tpch_session.execute(NO_FRAG + QUERIES[qid]).rows
        assert got == cpu_formulation_rows[qid] and len(got) > 0
        with ops._JIT_CACHE_LOCK:
            built = {ops.program_family(k) for k in ops._JIT_CACHE}
        assert "agg_partial" in built, built  # the device path ran it
        if qid in (3, 5, 9):
            assert "join_pairs" in built, built  # on the sorted join


def _clear_jit_cache():
    with ops._JIT_CACHE_LOCK:
        ops._JIT_CACHE.clear()


# -- persistent AOT compile cache ---------------------------------------------


def _restart(data_dir):
    """The validated restart recipe: drop every in-process compiled program
    (ours + jax's), zero the counters, boot a fresh Instance on the same
    data_dir.  Any steady-state program the new process compiles from
    scratch shows up as a retrace."""
    _clear_jit_cache()
    jax.clear_caches()
    ops.reset_compile_stats()
    return Instance(data_dir=str(data_dir))


def _seed_instance(data_dir):
    # fresh-process semantics: in production every program compiled after
    # boot is observed by the attached cache; here, earlier tests may have
    # compiled shared programs BEFORE attach (in-memory hits are never
    # observed), so start the seed process with an empty program set
    _clear_jit_cache()
    jax.clear_caches()
    inst = Instance(data_dir=str(data_dir))
    s = Session(inst)
    s.execute("CREATE DATABASE cc; USE cc")
    s.execute("CREATE TABLE t (g BIGINT, v BIGINT) "
              "PARTITION BY HASH(g) PARTITIONS 4")
    rng = np.random.default_rng(15)
    inst.store("cc", "t").insert_arrays(
        {"g": rng.integers(0, 25, 1500).astype(np.int64),
         "v": rng.integers(0, 500, 1500).astype(np.int64)},
        inst.tso.next_timestamp())
    return inst, s


QUERY = "SELECT g, SUM(v), COUNT(*) FROM t GROUP BY g ORDER BY g"


class TestCompileCachePersistence:
    def test_memory_only_instance_detaches(self):
        Instance()
        assert not GLOBAL_COMPILE_CACHE.attached

    def test_restart_round_trip_zero_steady_retraces(self, tmp_path):
        fallbacks0 = GLOBAL_COMPILE_CACHE.call_fallbacks
        inst, s = _seed_instance(tmp_path / "db")
        rows = s.execute(QUERY).rows
        s.execute(QUERY)  # steady
        inst.save()
        s.close()

        inst2 = _restart(tmp_path / "db")
        assert GLOBAL_COMPILE_CACHE.attached
        s2 = Session(inst2)
        s2.execute("USE cc")
        rows2 = s2.execute(QUERY).rows
        assert rows2 == rows
        assert ops.COMPILE_STATS["cache_hits"] > 0
        assert ops.COMPILE_STATS["retraces"] == 0
        # every restored program accepted its call: none was loaded for the
        # wrong devices and silently rebuilt (hit AND retrace)
        assert GLOBAL_COMPILE_CACHE.call_fallbacks == fallbacks0
        # and the replayed programs stay steady
        ops.reset_compile_stats()
        s2.execute(QUERY)
        assert ops.COMPILE_STATS["retraces"] == 0
        s2.close()

    def test_corrupted_entries_recompile_never_error(self, tmp_path):
        inst, s = _seed_instance(tmp_path / "db")
        rows = s.execute(QUERY).rows
        inst.save()
        s.close()

        cache_dir = tmp_path / "db" / "compile_cache"
        entries = sorted(cache_dir.glob("*.aot"))
        assert entries
        for p in entries:
            p.write_bytes(b"\x00garbage not a pickle\xff" * 7)

        inst2 = _restart(tmp_path / "db")
        s2 = Session(inst2)
        s2.execute("USE cc")
        assert s2.execute(QUERY).rows == rows  # recompiles, never errors
        assert ops.COMPILE_STATS["cache_hits"] == 0
        assert ops.COMPILE_STATS["retraces"] > 0
        # the bad entries were dropped so the next save can rewrite them
        assert not any(p.exists() for p in entries)
        s2.close()

    def test_compile_cache_metrics_surface(self, tmp_path):
        inst, s = _seed_instance(tmp_path / "db")
        s.execute(QUERY)
        inst.save()
        names = {r[0] for r in s.execute("SHOW METRICS").rows}
        assert {"compile_cache_hits", "compile_cache_misses",
                "compile_cache_bytes", "compile_cache_entries"} <= names
        s.close()

    def test_explain_analyze_reports_cached(self, tmp_path):
        inst, s = _seed_instance(tmp_path / "db")
        s.execute(QUERY)
        inst.save()
        s.close()
        inst2 = _restart(tmp_path / "db")
        s2 = Session(inst2)
        s2.execute("USE cc")
        text = "\n".join(str(r[0]) for r in
                         s2.execute("EXPLAIN ANALYZE " + QUERY).rows)
        assert "cached=" in text
        s2.close()

    def test_mesh_sharded_inputs_replay_from_disk(self, tmp_path):
        """A program whose steady-state args are mesh-sharded (MPP scan
        segments) must AOT-lower for that NamedSharding: without it the
        restored executable rejects every call and the disk hit degrades
        into a silent retrace."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-virtual-device mesh")
        mesh = Mesh(np.array(devs[:8]), ("shard",))
        sharded = jax.device_put(
            jnp.arange(8 * 1024, dtype=jnp.int64),
            NamedSharding(mesh, PartitionSpec("shard")))
        key = ("test", "sharded-replay")

        GLOBAL_COMPILE_CACHE.attach(str(tmp_path / "cc"))
        try:
            _clear_jit_cache()
            ops.reset_compile_stats()
            f = ops.global_jit(key, lambda: jax.jit(lambda a: a * 2 + 1))
            r1 = np.asarray(f(sharded))
            GLOBAL_COMPILE_CACHE.flush()

            _clear_jit_cache()
            jax.clear_caches()
            ops.reset_compile_stats()
            f2 = ops.global_jit(key, lambda: jax.jit(lambda a: a * 2 + 1))
            r2 = np.asarray(f2(sharded))
            np.testing.assert_array_equal(r1, r2)
            assert ops.COMPILE_STATS["cache_hits"] == 1
            # the loaded executable must ACCEPT the sharded call — a
            # call-time fallback would count a retrace here
            assert ops.COMPILE_STATS["retraces"] == 0
        finally:
            GLOBAL_COMPILE_CACHE.detach()
