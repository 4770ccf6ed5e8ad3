"""Decisions of `HashJoinOp` that a large build side reaches (TPC-H SF10's Q3
and Q5 did, `tpch_sf10.join`): which joins build a join-local bloom filter (only
those whose next program pays by the live probe row), the bloom filter of a
build side over `BLOOM_MAX_BUILD` rows, and the grace spill of a build side
that arrives at an upstream join's pair capacity, mostly dead slots.  The
limits are brought down to sizes a test can hold; the chip's formulations on
this CPU."""

import numpy as np
import pytest

import jax.numpy as jnp

from galaxysql_tpu import native
from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.operators import HashJoinOp, SourceOp
from galaxysql_tpu.expr import ir
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import tracing

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
    """A sparse build side `_materialize_build` compacted on the host could
    have its filter built there; an inner join on the sorted formulation
    builds none, since its count, merge and expansion pay by the probe slot
    and a bloom-negative row counts no pair."""
    monkeypatch.setattr(HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    want = [(k, 2 * k) for k in range(0, 3000 * 7, 7)]
    before = ops.JOIN_STATS["blooms"]
    op = HashJoinOp(SourceOp([sparse_build()]), SourceOp([probe()]), [K], [PK])
    assert joined(op) == want
    # the pair capacity is the bucket of the 3,000 pairs counted
    assert "bloom_query" not in families()
    assert ops.JOIN_STATS["blooms"] == before
    assert pair_caps() == [4096]


def test_a_dense_build_side_on_the_device_still_gets_none(
        chip_formulation, monkeypatch):
    monkeypatch.setattr(HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    keys = np.arange(4096, dtype=np.int64) * 7
    dense = side("b", keys, np.ones(4096, bool))
    op = HashJoinOp(SourceOp([dense]), SourceOp([probe()]), [K], [PK])
    assert joined(op) == [(k, 2 * k) for k in keys.tolist() if k < 65536]
    # over the limit, a bucket's worth of slots, all live: the side stays on
    # the device and nothing crosses the host link for a filter (and the pair
    # capacity is the bucket of the 4,096 pairs, not twice the 65,536 probe
    # rows the filter would have cut)
    assert "bloom_query" not in families()
    assert pair_caps() == [4096]


XB = ir.ColRef("b.x", dt.BIGINT)
XP = ir.ColRef("p.x", dt.BIGINT)
AT_LEAST = ir.Call("ge", [XB, XP], dt.BOOL)


def keyed(prefix: str, k: np.ndarray, x: np.ndarray) -> ColumnBatch:
    return ColumnBatch(
        {f"{prefix}.k": Column(jnp.asarray(k), None, dt.BIGINT, None),
         f"{prefix}.x": Column(jnp.asarray(x), None, dt.BIGINT, None)},
        None)


def two_sides():
    """300 build keys (0, 10, ..., 2990) of two rows each under 16,384 probe
    rows drawn from 0-59,999: about 1.5% of the probe rows have a key, so a
    filter leaves the probe side mostly dead."""
    rng = np.random.default_rng(41)
    bk = np.repeat(np.arange(0, 3000, 10, dtype=np.int64), 2)
    bx = rng.integers(0, 10, bk.size).astype(np.int64)
    pk = rng.integers(0, 60000, 16384).astype(np.int64)
    px = rng.integers(0, 10, pk.size).astype(np.int64)
    return (bk, bx), (pk, px)


def traced_rows(op, trace_id: int):
    """The join's rows as (column -> value) tuples, sorted, and its span."""
    tc = tracing.TraceContext(trace_id)
    span = tc.add("Join", kind="operator")
    tc.cursor = span.span_id
    with tracing.activate(tc):
        out = ops.run_to_batch(op)
    live = out.np_live()
    cols = sorted(out.columns)
    got = sorted(zip(*(out.columns[c].np_data()[live].tolist()
                       for c in cols)))
    return cols, got, span


def reference(kind, residual, b, p):
    """numpy's answer: inner rows as (b.k, b.x, p.k, p.x), semi as (p.k, p.x)."""
    (bk, bx), (pk, px) = b, p
    eq = pk[:, None] == bk[None, :]
    if residual is not None:
        eq &= bx[None, :] >= px[:, None]
    if kind == "inner":
        pi, bi = np.nonzero(eq)
        return sorted(zip(bk[bi].tolist(), bx[bi].tolist(),
                          pk[pi].tolist(), px[pi].tolist()))
    keep = eq.any(axis=1)
    return sorted(zip(pk[keep].tolist(), px[keep].tolist()))


@pytest.mark.parametrize("kind, residual, bloom", [
    ("inner", None, False), ("semi", AT_LEAST, False), ("semi", None, True)],
    ids=["inner", "semi with a residual", "semi, matched only"])
def test_the_sorted_formulation_builds_a_bloom_only_where_a_live_row_is_paid(
        kind, residual, bloom, chip_formulation):
    b, p = two_sides()
    before = dict(ops.JOIN_STATS)
    op = HashJoinOp(SourceOp([keyed("b", *b)]), SourceOp([keyed("p", *p)]),
                    [K], [PK], kind, residual=residual)
    cols, got, span = traced_rows(op, 41)
    want = reference(kind, residual, b, p)
    assert got == want and len(want) > 0
    assert cols == (["b.k", "b.x", "p.k", "p.x"] if kind == "inner"
                    else ["p.k", "p.x"])
    assert ops.JOIN_STATS["blooms"] == before["blooms"] + int(bloom)
    assert span.attrs["bloom"] == int(bloom)
    assert ("bloom_query" in families()) == bloom
    if bloom:
        # the filter left some 1.5% of 16,384 probe rows live (and its false
        # positives): they are looked up in the bucket they fill
        (slots,) = [k[-1] for k in ops._JIT_CACHE
                    if k[0] == "join_pairs" and k[2] == "matched"]
        assert slots is not None and 2 * slots <= 16384
    else:
        assert ops.JOIN_STATS["pairs"] > before["pairs"]


def test_the_slot_table_formulation_still_builds_an_inner_joins_bloom(
        monkeypatch):
    """The slot table's lookup pays by the live row (a slot's other keys are
    candidates): there an inner join keeps its filter."""
    assert ops.K.prefer_scatter()
    monkeypatch.setattr(native, "AVAILABLE", False)  # not the native table
    b, p = two_sides()
    before = ops.JOIN_STATS["blooms"]
    op = HashJoinOp(SourceOp([keyed("b", *b)]), SourceOp([keyed("p", *p)]),
                    [K], [PK])
    _, got, span = traced_rows(op, 42)
    assert got == reference("inner", None, b, p)
    assert ops.JOIN_STATS["blooms"] == before + 1
    assert span.attrs["bloom"] == 1


@pytest.mark.parametrize("kind, residual", [("inner", None),
                                            ("semi", AT_LEAST)],
                         ids=["inner", "semi with a residual"])
def test_the_pairs_counted_do_not_depend_on_enable_bloom(
        kind, residual, chip_formulation):
    b, p = two_sides()
    counted = []
    for enable in (True, False):
        before = ops.JOIN_STATS["pairs"]
        op = HashJoinOp(SourceOp([keyed("b", *b)]), SourceOp([keyed("p", *p)]),
                        [K], [PK], kind, residual=residual,
                        enable_bloom=enable)
        _, got, _ = traced_rows(op, 43)
        assert got == reference(kind, residual, b, p)
        counted.append(ops.JOIN_STATS["pairs"] - before)
    assert counted[0] == counted[1] > 0


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
