"""The CPU's scatter/hash formulations on their own contracts (float keys,
exact int64 sums, the dense-slot layout against `matmul_groupby`, the join's
overflow flag), and literal lifting (template compile keys).

The CPU twins exist because XLA:CPU inverts TPU's cost model (scatters are
native loops, comparator sorts are single-threaded).  That `hash_groupby` and
`_hash_join_pairs_table` give the relations of the TPU's sort formulations is
the matrix of `tests/test_kernels.py::TestFormulationEquivalence`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galaxysql_tpu.kernels import relational as K


def _groups(r: K.GroupByResult):
    """{key tuple: agg tuple} over live slots; NULL encoded as None."""
    live = np.asarray(r.live)
    out = {}
    for i in np.nonzero(live)[0]:
        key = tuple(
            None if (v is not None and not bool(np.asarray(v)[i]))
            else np.asarray(d)[i].item() for d, v in r.keys)
        aggs = tuple(
            None if (v is not None and not bool(np.asarray(v)[i]))
            else np.asarray(d)[i].item() for d, v in r.aggs)
        out[key] = aggs
    return out


SPECS = [K.AggSpec("sum", 0), K.AggSpec("count", 0), K.AggSpec("count_star", -1),
         K.AggSpec("min", 0), K.AggSpec("max", 0)]


class TestHashGroupby:
    def test_float_keys_nan_negzero_one_group(self):
        # SQL GROUP BY: all NaNs one group, -0.0 == 0.0
        f = jnp.asarray(np.array([np.nan, np.nan, -0.0, 0.0, 1.5, 1.5, np.nan]))
        x = jnp.asarray(np.arange(7, dtype=np.int64))
        r = K.hash_groupby([(f, None)], [(x, None)],
                           [K.AggSpec("count_star", -1)], jnp.ones(7, bool), 16)
        assert int(r.num_groups) == 3
        counts = sorted(v[0] for v in _groups(r).values())
        assert counts == [2, 2, 3]

    def test_int64_sums_exact_beyond_f64(self):
        big = 1 << 60
        x = jnp.asarray(np.array([big, big, big, -5], dtype=np.int64))
        k = jnp.asarray(np.zeros(4, np.int32))
        r = K.hash_groupby([(k, None)], [(x, None)], [K.AggSpec("sum", 0)],
                           jnp.ones(4, bool), 16)
        want = (np.int64(big) * 3 - 5).item()
        assert list(_groups(r).values())[0][0] == want


class TestScatterGroupby:
    def test_matches_matmul_groupby(self):
        rng = np.random.default_rng(11)
        n = 8000
        k1 = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))
        k1v = jnp.asarray(rng.random(n) > 0.1)
        k2 = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
        x = jnp.asarray(rng.integers(-10**11, 10**11, n))
        xv = jnp.asarray(rng.random(n) > 0.2)
        live = jnp.asarray(rng.random(n) > 0.15)
        a = K.scatter_groupby([(k1, k1v), (k2, None)], [(x, xv)], SPECS,
                              live, [3, 2])
        b = K.matmul_groupby([(k1, k1v), (k2, None)], [(x, xv)], SPECS,
                             live, [3, 2])
        assert _groups(a) == _groups(b)
        # identical slot layout (domain cross product), not just same groups
        assert (np.asarray(a.live) == np.asarray(b.live)).all()

    def test_float_sum_supported(self):
        # the matmul byte-limb path rejects float sums; scatter handles them
        n = 1000
        rng = np.random.default_rng(3)
        k = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
        f = jnp.asarray(rng.standard_normal(n))
        a = K.scatter_groupby([(k, None)], [(f, None)],
                              [K.AggSpec("sum", 0)], jnp.ones(n, bool), [2])
        want0 = np.asarray(f)[np.asarray(k) == 0].sum()
        got0 = np.asarray(a.aggs[0][0])[0]
        assert abs(got0 - want0) < 1e-9


class TestTableJoin:
    def test_overflow_reported(self):
        # every probe row matches every build row: cap too small must flag
        nb, npr = 128, 128
        k = jnp.zeros(nb, jnp.int64)
        r = K._hash_join_pairs_table([(k, None)], [(jnp.zeros(npr, jnp.int64), None)],
                                     jnp.ones(nb, bool), jnp.ones(npr, bool), 256)
        assert bool(r.overflow)


class TestLiteralLifting:
    def test_template_key_value_independent(self):
        from galaxysql_tpu.expr import ir
        from galaxysql_tpu.expr.compiler import LiftedLiterals
        from galaxysql_tpu.types import datatype as dt
        col = ir.ColRef("c", dt.BIGINT)
        e1 = ir.call("eq", col, ir.lit(7))
        e2 = ir.call("eq", col, ir.lit(9))
        l1, l2 = LiftedLiterals([e1]), LiftedLiterals([e2])
        assert l1.template_key(e1) == l2.template_key(e2)
        assert l1.values() != l2.values()

    def test_distinct_literals_share_compiled_kernel(self):
        from galaxysql_tpu.exec.operators import _JIT_CACHE, FilterOp, SourceOp
        from galaxysql_tpu.chunk.batch import Column, ColumnBatch
        from galaxysql_tpu.expr import ir
        from galaxysql_tpu.types import datatype as dt

        col = Column(jnp.arange(64, dtype=jnp.int64), None, dt.BIGINT, None)
        batch = ColumnBatch({"c": col}, jnp.ones(64, bool))
        colref = ir.ColRef("c", dt.BIGINT)

        def run(v):
            op = FilterOp(SourceOp([batch]), ir.call("eq", colref, ir.lit(v)))
            out = list(op.batches())[0]
            return int(np.asarray(out.live_mask()).sum())

        run(3)
        before = len(_JIT_CACHE)
        assert run(5) == 1 and run(41) == 1
        assert len(_JIT_CACHE) == before  # no new kernels for new literals
