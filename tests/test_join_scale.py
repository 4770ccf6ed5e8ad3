"""Two decisions of `HashJoinOp` that only a large build side reaches (TPC-H
SF10's Q3 and Q5 did, `tpch_sf10.join`): the bloom filter of a build side over
`BLOOM_MAX_BUILD` rows, and the grace spill of a build side that arrives at an
upstream join's pair capacity, mostly dead slots.  The limits are brought down
to sizes a test can hold; the chip's formulations on this CPU."""

import numpy as np
import pytest

import jax.numpy as jnp

from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.operators import HashJoinOp, SourceOp
from galaxysql_tpu.expr import ir
from galaxysql_tpu.types import datatype as dt

K = ir.ColRef("b.k", dt.BIGINT)
PK = ir.ColRef("p.k", dt.BIGINT)


def side(prefix: str, keys: np.ndarray, live: np.ndarray) -> ColumnBatch:
    return ColumnBatch(
        {f"{prefix}.k": Column(jnp.asarray(keys), None, dt.BIGINT, None),
         f"{prefix}.x": Column(jnp.asarray(keys * 2), None, dt.BIGINT, None)},
        jnp.asarray(live))


def sparse_build(slots: int = 16384, rows: int = 3000) -> ColumnBatch:
    """`rows` live keys 0, 7, 14, ... scattered over `slots` slots: what a join
    hands on at its pair capacity."""
    keys = np.full(slots, -1, np.int64)
    at = np.sort(np.random.default_rng(2).choice(slots, rows, replace=False))
    keys[at] = np.arange(rows) * 7
    return side("b", keys, keys >= 0)


def probe(n: int = 65536) -> ColumnBatch:
    return side("p", np.arange(n, dtype=np.int64), np.ones(n, bool))


def joined(op) -> list:
    out = ops.run_to_batch(op)
    live = out.np_live()
    return sorted(zip(out.columns["p.k"].np_data()[live].tolist(),
                      out.columns["b.x"].np_data()[live].tolist()))


def pair_caps() -> list:
    return sorted(p.key[2] for p in ops.PROGRAMS.entries()
                  if p.family == "join_pairs" and isinstance(p.key[2], int))


def families() -> set:
    return {p.family for p in ops.PROGRAMS.entries()}


def test_a_build_side_compacted_on_the_host_keeps_its_bloom_filter(
        chip_formulation, monkeypatch):
    monkeypatch.setattr(HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    want = [(k, 2 * k) for k in range(0, 3000 * 7, 7)]
    op = HashJoinOp(SourceOp([sparse_build()]), SourceOp([probe()]), [K], [PK])
    assert joined(op) == want
    # 3,000 build rows are over the limit, but `_materialize_build` read the
    # sparse side to the host, where the filter is built: the pair capacity
    # starts from the probe rows the filter let through, not from 65,536
    assert "bloom_query" in families()
    assert pair_caps() == [8192]


def test_a_dense_build_side_on_the_device_still_gets_none(
        chip_formulation, monkeypatch):
    monkeypatch.setattr(HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    keys = np.arange(4096, dtype=np.int64) * 7
    dense = side("b", keys, np.ones(4096, bool))
    op = HashJoinOp(SourceOp([dense]), SourceOp([probe()]), [K], [PK])
    assert joined(op) == [(k, 2 * k) for k in keys.tolist() if k < 65536]
    # over the limit, a bucket's worth of slots, all live: the side stays on
    # the device and nothing crosses the host link for a filter
    assert "bloom_query" not in families()
    assert pair_caps() == [131072]


@pytest.mark.parametrize("rows, graced", [(3000, False), (12000, True)])
def test_the_grace_spill_is_decided_by_a_build_sides_live_rows(rows, graced):
    build = sparse_build(slots=16384, rows=rows)      # 278,528 bytes of slots
    want = [(k, 2 * k) for k in range(0, rows * 7, 7) if k < 65536]
    op = HashJoinOp(SourceOp([build]), SourceOp([probe()]), [K], [PK],
                    spill_threshold=128 << 10)
    assert joined(op) == want
    # 3,000 live rows hold 51,000 bytes once materialized; 12,000 hold 204,000
    assert (op.grace_partitions > 0) == graced


@pytest.mark.parametrize("rows, bits", [
    (1000, 1 << 14), (228_000, 1 << 22), (730_000, 1 << 22), (1 << 20, 1 << 22),
    ((1 << 20) + 1, 1 << 23), (2_280_000, 1 << 24), (1 << 22, 1 << 24)])
def test_a_published_bloom_filter_keeps_four_bits_a_key(rows, bits):
    """Up to a million build rows the flags are what they were (sixteen bits a
    key under a 4 MB ceiling, so SF1's filters and the programs keyed by their
    size stand); above it the ceiling follows the rows at four bits a key, up
    to the most rows a build side publishes at all."""
    from galaxysql_tpu.exec import runtime_filter as rf
    keys = np.arange(rows, dtype=np.int64) * 4 + 1
    f = rf.RuntimeFilter.build(keys, {"bloom", "minmax"})
    assert f.nbits == bits and f.flags.shape == (bits,)
    assert (f.lo, f.hi) == (1, 4 * rows - 3)
    probe = np.concatenate([keys[:1000], keys[:1000] + 1])     # in, then out
    b1, b2 = rf._bloom_positions(np, probe, bits)
    hit = (f.flags[b1] & f.flags[b2]) > 0
    assert hit[:1000].all() and hit[1000:].mean() < 0.25


def test_no_bloom_filter_past_the_rows_a_build_side_publishes():
    from galaxysql_tpu.exec import runtime_filter as rf
    assert rf.RF_BLOOM_MAX_BUILD == rf.RF_PUBLISH_MAX_ROWS
    keys = np.arange(rf.RF_PUBLISH_MAX_ROWS + 1, dtype=np.int64)
    f = rf.RuntimeFilter.build(keys, {"bloom", "minmax"})
    assert f.flags is None and f.nbits == 0 and f.lo == 0
