"""Kernel tier: Pallas join/agg kernels + persistent AOT compile cache.

Coverage: Pallas-vs-reference bit-identity on the direct kernel matrix (NULL
keys, empty build, duplicate keys, overflow-ladder doubling, both hybrid
orientations) and on TPC-H Q5/Q9 end-to-end via the KERNEL hint; the
escape-hatch trio proven structurally off-path with trace-time selection
counters (`KERNEL_STATS`) and dispatch-count guards (the SHOW PROFILES
unchanged-dispatch idiom extended to the kernel selector); persistent
AOT-cache restart round trip (save -> boot -> same query with zero steady
retraces and cache hits > 0), corrupted-entry recompile tolerance, and the
compile_cache_* observability surfaces.  Fast target: make kernel-smoke.
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


def _leaves(result):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(result)]


def _assert_bit_identical(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


def _groupby(mode, keys, inputs, specs, live, max_groups, max_rounds=64):
    with R.kernel_scope(mode):
        return R.hash_groupby(_lanes(keys), _lanes(inputs), specs,
                              jnp.asarray(live), max_groups, max_rounds)


def _join(mode, bk, pk, b_live, p_live, cap):
    with R.kernel_scope(mode):
        return R.hash_join_pairs(_lanes(bk), _lanes(pk), jnp.asarray(b_live),
                                 jnp.asarray(p_live), cap)


def _hybrid(mode, bk, pk, b_live, p_live, cap):
    with R.kernel_scope(mode):
        return R.hash_join_probe_hybrid(_lanes(bk), _lanes(pk),
                                        jnp.asarray(b_live),
                                        jnp.asarray(p_live), cap)


# -- Pallas vs reference: direct kernel bit-identity matrix -------------------


class TestPallasBitIdentity:
    """`kernel_scope('pallas')` forces the Pallas formulation (interpret mode
    on CPU); `'off'` forces the reference formulation, which is the
    correctness oracle.  Everything — group placement order, pair slot
    layout, overflow flags — must be BIT-identical, because the Pallas
    kernels reimplement the same deterministic algorithm, not merely the
    same relation."""

    def test_groupby_duplicate_keys(self):
        rng = np.random.default_rng(7)
        n = 1536
        k = rng.integers(0, 53, n).astype(np.int64)  # heavy duplication
        v = rng.integers(-1000, 1000, n).astype(np.int64)
        keys = [(k, None)]
        inputs = [(v, None), (k, None)]
        specs = [R.AggSpec("sum", 0), R.AggSpec("count_star", -1),
                 R.AggSpec("min", 1)]
        live = np.ones(n, bool)
        ref = _groupby("off", keys, inputs, specs, live, 256)
        pal = _groupby("pallas", keys, inputs, specs, live, 256)
        assert not bool(ref.overflow)
        _assert_bit_identical(ref, pal)

    def test_groupby_null_keys(self):
        rng = np.random.default_rng(8)
        n = 1024
        k1 = rng.integers(0, 31, n).astype(np.int64)
        k2 = rng.integers(0, 5, n).astype(np.int64)
        valid1 = rng.random(n) > 0.2  # NULLs form their own groups
        v = rng.integers(0, 100, n).astype(np.int64)
        keys = [(k1, valid1), (k2, None)]
        inputs = [(v, None)]
        specs = [R.AggSpec("sum", 0), R.AggSpec("count_star", -1)]
        live = rng.random(n) > 0.1
        ref = _groupby("off", keys, inputs, specs, live, 512)
        pal = _groupby("pallas", keys, inputs, specs, live, 512)
        _assert_bit_identical(ref, pal)

    def test_groupby_empty_input(self):
        # zero LIVE rows at positive static capacity — the engine's "empty"
        n = 256
        keys = [(np.zeros(n, np.int64), None)]
        inputs = [(np.zeros(n, np.int64), None)]
        specs = [R.AggSpec("sum", 0)]
        live = np.zeros(n, bool)
        ref = _groupby("off", keys, inputs, specs, live, 64)
        pal = _groupby("pallas", keys, inputs, specs, live, 64)
        assert int(ref.num_groups) == 0
        _assert_bit_identical(ref, pal)

    def test_groupby_overflow_ladder_doubling(self):
        """Overflow semantics ARE the ladder contract: both formulations must
        overflow at the same undersized capacity and both must succeed —
        bit-identically — after one doubling."""
        rng = np.random.default_rng(9)
        n = 512
        k = rng.permutation(n).astype(np.int64)  # n distinct groups
        keys = [(k, None)]
        inputs = [(k, None)]
        specs = [R.AggSpec("count_star", -1)]
        live = np.ones(n, bool)
        ref_s = _groupby("off", keys, inputs, specs, live, 16, max_rounds=8)
        pal_s = _groupby("pallas", keys, inputs, specs, live, 16, max_rounds=8)
        assert bool(ref_s.overflow) and bool(pal_s.overflow)
        ref_b = _groupby("off", keys, inputs, specs, live, 1024)
        pal_b = _groupby("pallas", keys, inputs, specs, live, 1024)
        assert not bool(ref_b.overflow) and not bool(pal_b.overflow)
        _assert_bit_identical(ref_b, pal_b)

    def test_join_pairs_duplicates_and_nulls(self):
        rng = np.random.default_rng(10)
        nb, npr = 512, 1024
        bk = rng.integers(0, 37, nb).astype(np.int64)
        pk = rng.integers(0, 50, npr).astype(np.int64)
        bv = rng.random(nb) > 0.15  # NULL build keys never match
        pv = rng.random(npr) > 0.15
        cap = 16 * npr
        ref = _join("off", [(bk, bv)], [(pk, pv)], np.ones(nb, bool),
                    np.ones(npr, bool), cap)
        pal = _join("pallas", [(bk, bv)], [(pk, pv)], np.ones(nb, bool),
                    np.ones(npr, bool), cap)
        assert not bool(ref.overflow)
        _assert_bit_identical(ref, pal)

    def test_join_empty_build(self):
        nb, npr = 128, 256
        bk = np.zeros(nb, np.int64)
        pk = np.zeros(npr, np.int64)
        ref = _join("off", [(bk, None)], [(pk, None)], np.zeros(nb, bool),
                    np.ones(npr, bool), npr)
        pal = _join("pallas", [(bk, None)], [(pk, None)], np.zeros(nb, bool),
                    np.ones(npr, bool), npr)
        assert not np.asarray(ref.live).any()
        _assert_bit_identical(ref, pal)

    @pytest.mark.parametrize("orientation", ["skewed_probe", "skewed_build"])
    def test_hybrid_orientations(self, orientation):
        """The hybrid entry now rides the CSR probe on every backend
        (previously a bare `hash_join_pairs` delegation), so the Pallas
        kernels must reproduce its layout for BOTH skew orientations."""
        rng = np.random.default_rng(11)
        if orientation == "skewed_probe":
            nb, npr, hot_side = 256, 2048, "p"
        else:
            nb, npr, hot_side = 2048, 256, "b"
        bk = rng.integers(0, 40, nb).astype(np.int64)
        pk = rng.integers(0, 40, npr).astype(np.int64)
        hot = bk if hot_side == "b" else pk
        hot[: len(hot) // 2] = 7  # one dominant key
        cap = 8 * max(nb, npr)
        ref = _hybrid("off", [(bk, None)], [(pk, None)], np.ones(nb, bool),
                      np.ones(npr, bool), cap)
        pal = _hybrid("pallas", [(bk, None)], [(pk, None)], np.ones(nb, bool),
                      np.ones(npr, bool), cap)
        assert not bool(ref.overflow)
        _assert_bit_identical(ref, pal)


# -- escape hatches + dispatch guards -----------------------------------------


def _clear_jit_cache():
    with ops._JIT_CACHE_LOCK:
        ops._JIT_CACHE.clear()


def _reset_kernel_stats():
    R.KERNEL_STATS["pallas"] = 0
    R.KERNEL_STATS["reference"] = 0


class TestKernelSelector:
    """The hatch trio must be STRUCTURALLY off-path: with a hatch engaged,
    tracing a program never even consults the Pallas formulation
    (`KERNEL_STATS['pallas']` stays zero) — not merely that results agree."""

    def test_env_hatch_beats_forced_pallas(self, monkeypatch):
        monkeypatch.setattr(R, "_PALLAS_ENV_OFF", True)
        _clear_jit_cache()
        _reset_kernel_stats()
        n = 300
        keys = [(np.arange(n, dtype=np.int64) % 11, None)]
        specs = [R.AggSpec("count_star", -1)]
        _groupby("pallas", keys, [], specs, np.ones(n, bool), 64)
        assert R.KERNEL_STATS["pallas"] == 0
        assert R.KERNEL_STATS["reference"] > 0

    def test_mode_resolution_precedence(self):
        inst = Instance()
        assert R.exec_kernel_mode({"kernel": "off"}, inst) == "off"
        assert R.exec_kernel_mode({"kernel": "pallas"}, inst) == "pallas"
        assert R.exec_kernel_mode({}, inst) == "auto"
        inst.config.set_instance("ENABLE_PALLAS_KERNELS", False)
        assert R.exec_kernel_mode({}, inst) == "off"
        # KERNEL(ON) restores auto selection under a disabling param
        assert R.exec_kernel_mode({"kernel": "on"}, inst) == "auto"

    def test_auto_mode_on_cpu_keeps_reference(self):
        # CPU backend: auto never picks Pallas regardless of row count
        _clear_jit_cache()
        _reset_kernel_stats()
        n = 400
        keys = [(np.arange(n, dtype=np.int64) % 13, None)]
        _groupby("auto", keys, [], [R.AggSpec("count_star", -1)],
                 np.ones(n, bool), 64)
        assert R.KERNEL_STATS["pallas"] == 0

    def test_session_hatches_off_path_and_hint_engages(self):
        # AP-scale rows (> AP_ROW_THRESHOLD): the query must reach the DEVICE
        # aggregation kernels — a host-TP-path query never consults the
        # selector and would prove nothing
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE kt; USE kt")
        s.execute("CREATE TABLE t (g BIGINT, v BIGINT) "
                  "PARTITION BY HASH(g) PARTITIONS 4")
        rng = np.random.default_rng(12)
        n = 70_000
        inst.store("kt", "t").insert_arrays(
            {"g": rng.integers(0, 40, n).astype(np.int64),
             "v": rng.integers(0, 1000, n).astype(np.int64)},
            inst.tso.next_timestamp())
        inst.config.set_instance("MPP_MIN_AP_ROWS", 1)  # force mesh execution
        q = "SELECT g, SUM(v), COUNT(*) FROM t GROUP BY g ORDER BY g"

        def fresh():
            # every run must actually TRACE: drop compiled programs AND the
            # fragment cache (a replayed fragment is bit-identical across
            # formulations, so serving it is sound — but it would hide the
            # selector from this structural guard)
            _clear_jit_cache()
            inst.frag_cache.clear()
            _reset_kernel_stats()

        fresh()
        base = s.execute(q)  # default auto on CPU
        assert R.KERNEL_STATS["pallas"] == 0

        fresh()
        off = s.execute("/*+TDDL:KERNEL(OFF)*/ " + q)
        assert R.KERNEL_STATS["pallas"] == 0

        inst.config.set_instance("ENABLE_PALLAS_KERNELS", False)
        fresh()
        param_off = s.execute(q)
        assert R.KERNEL_STATS["pallas"] == 0
        inst.config.set_instance("ENABLE_PALLAS_KERNELS", True)

        fresh()
        pal = s.execute("/*+TDDL:KERNEL(PALLAS)*/ " + q)
        assert R.KERNEL_STATS["pallas"] > 0  # the hint reached the selector
        assert base.rows == off.rows == param_off.rows == pal.rows
        s.close()

    def test_dispatch_count_kernel_off_equals_default(self):
        """SKEW(OFF)-style guard: on CPU the default path IS the reference
        formulation, so a KERNEL(OFF) hint compiles a twin program with the
        exact same dispatch count."""
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE kd; USE kd")
        s.execute("CREATE TABLE t (g BIGINT, v BIGINT) "
                  "PARTITION BY HASH(g) PARTITIONS 4")
        rng = np.random.default_rng(13)
        n = 70_000
        inst.store("kd", "t").insert_arrays(
            {"g": rng.integers(0, 20, n).astype(np.int64),
             "v": rng.integers(0, 100, n).astype(np.int64)},
            inst.tso.next_timestamp())
        inst.config.set_instance("MPP_MIN_AP_ROWS", 1)  # force mesh execution
        q = "SELECT g, SUM(v) FROM t GROUP BY g"

        def dispatches(sql):
            s.execute(sql)  # warmup/compile
            ops.reset_dispatch_stats()
            s.execute(sql)
            return ops.DISPATCH_STATS["dispatches"]

        assert dispatches(q) == dispatches("/*+TDDL:KERNEL(OFF)*/ " + q)
        s.close()

    def test_steady_dispatches_unchanged_after_pallas_run(self):
        """The SHOW PROFILES unchanged-dispatch guard, extended to the kernel
        selector: a KERNEL(PALLAS)-hinted run compiles a DIFFERENT program
        (the mode rides the global_jit key) and must not perturb subsequent
        default executions — same dispatch count, zero retraces."""
        inst = Instance()
        s = Session(inst)
        s.execute("CREATE DATABASE kg; USE kg")
        s.execute("CREATE TABLE t (g BIGINT, v BIGINT) "
                  "PARTITION BY HASH(g) PARTITIONS 4")
        rng = np.random.default_rng(14)
        n = 70_000
        inst.store("kg", "t").insert_arrays(
            {"g": rng.integers(0, 16, n).astype(np.int64),
             "v": rng.integers(0, 100, n).astype(np.int64)},
            inst.tso.next_timestamp())
        inst.config.set_instance("MPP_MIN_AP_ROWS", 1)  # force mesh execution
        q = "SELECT g, COUNT(*) FROM t GROUP BY g"
        s.execute(q)  # warmup
        ops.reset_dispatch_stats()
        s.execute(q)
        baseline = ops.DISPATCH_STATS["dispatches"]
        s.execute("/*+TDDL:KERNEL(PALLAS)*/ " + q)  # may dispatch differently
        ops.reset_dispatch_stats()
        ops.reset_compile_stats()
        s.execute(q)
        assert ops.DISPATCH_STATS["dispatches"] == baseline
        assert ops.COMPILE_STATS["retraces"] == 0
        s.close()


# -- TPC-H end-to-end equivalence ---------------------------------------------


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


class TestTpchKernelEquivalence:
    @pytest.mark.parametrize("qid", [5, 9])
    def test_kernel_on_equals_off(self, tpch_session, qid):
        from galaxysql_tpu.storage.tpch_queries import QUERIES
        s = tpch_session
        off = s.execute("/*+TDDL:KERNEL(OFF)*/ " + QUERIES[qid])
        default = s.execute(QUERIES[qid])
        on = s.execute("/*+TDDL:KERNEL(PALLAS)*/ " + QUERIES[qid])
        assert off.rows == default.rows == on.rows


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
