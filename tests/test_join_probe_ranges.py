"""The TPU join's range lookup, `K._merge_ranges` (both sides' hashes sorted as
one lane, a running count of the build slots, a sort back), against a NumPy
oracle that searches the whole sorted build lane twice, as the formulation
once did: every leaf of `JoinPairs` equal, the lookup alone bit for bit
`np.searchsorted`'s left and right at every ratio of the two sides a cell of
the benchmark has, the pair-less probe (`K.hash_join_matched`) whole and
through a front bucket, and the same function under `shard_map`; the ragged
expansion (`K._expand_rows`: one scatter, a running maximum by doubling
strides) against the same oracle's full-depth search for every pair slot; and
what the chip's compiler is handed: a probe scope of two sorts and no gather.

The sorted formulation is called directly: on this backend `hash_join_pairs`
picks the slot-table one."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galaxysql_tpu.kernels import relational as K

DEAD = np.uint64(0xffffffffffffffff)
def _np_live(keys, live):
    m = np.asarray(live)
    for _, v in keys:
        if v is not None:
            m = m & np.asarray(v)
    return m


def oracle(build_keys, probe_keys, build_live, probe_live, cap) -> K.JoinPairs:
    """Two full-depth searches of the stably sorted build hashes, in NumPy."""
    b_live, p_live = _np_live(build_keys, build_live), _np_live(probe_keys, probe_live)
    nb, npr = b_live.shape[0], p_live.shape[0]
    if nb == 0 or npr == 0:  # no candidate: every slot dead and zero
        none, ends = np.zeros(cap, np.int32), np.zeros(npr, np.int64)
        return K.JoinPairs(none, none, np.zeros(cap, bool), np.zeros(npr, bool),
                           ends, ends, np.bool_(False))
    top = DEAD - np.uint64(1)
    h_b = np.where(b_live, np.minimum(np.asarray(K.hash_columns(build_keys)), top), DEAD)
    h_p = np.minimum(np.asarray(K.hash_columns(probe_keys)), top)
    perm = np.argsort(h_b, kind="stable")
    h_sorted = h_b[perm]
    left, right = _searchsorteds(h_sorted, h_p)
    counts = np.where(p_live, right - left, 0).astype(np.int64)
    offsets = np.cumsum(counts)
    total = offsets[-1]
    starts = offsets - counts
    slots = np.arange(cap, dtype=np.int64)
    p_of = np.clip(np.searchsorted(offsets, slots, side="right"), 0,
                   npr - 1).astype(np.int32)
    k = slots - np.asarray(starts)[p_of]
    pair_live = slots < min(total, cap)
    bpos = np.clip(np.asarray(left)[p_of].astype(np.int32) + k.astype(np.int32), 0,
                   nb - 1)
    b_of = np.asarray(perm)[bpos].astype(np.int32)
    verified = pair_live & np.asarray(b_live)[b_of] & np.asarray(p_live)[p_of]
    for (bd, _), (pd, _) in zip(build_keys, probe_keys):
        verified = verified & (np.asarray(bd)[b_of] == np.asarray(pd)[p_of])
    c = np.concatenate([[0], np.cumsum(verified)])
    matched = (c[np.clip(offsets, 0, cap)] - c[np.clip(starts, 0, cap)]) > 0
    return K.JoinPairs(b_of, p_of, verified, matched, starts, offsets,
                       np.bool_(total > cap))


def _searchsorteds(h_sorted, h_p):
    """`np.searchsorted` left and right of the sorted build hashes, over the
    upper 63 bits of the live rows (the lookup's lane keeps the lowest bit for
    the side; the dead rows sort behind the live ones, so a position among the
    live rows is the position in the whole lane)."""
    h_sorted, h_p = h_sorted[h_sorted != DEAD] >> np.uint64(1), h_p >> np.uint64(1)
    return (np.searchsorted(h_sorted, h_p, side="left"),
            np.searchsorted(h_sorted, h_p, side="right"))


def expand_passes(starts, offsets, cap) -> np.int32:
    """Passes `K._expand_rows` has to report: `bit_length` of the farthest a
    slot with a pair lies from its row's first slot."""
    starts, offsets = np.asarray(starts), np.asarray(offsets)
    if not offsets.size:
        return np.int32(0)
    filled = min(int(offsets[-1]), cap)
    farthest = np.minimum(offsets, filled) - 1 - starts  # below 0: no pair, or past `cap`
    return np.int32(max(int(farthest.max()), 0).bit_length())


def with_expand_passes(want: K.JoinPairs, cap) -> K.JoinPairs:
    """The oracle's leaves, with the one it does not build."""
    return want._replace(expand_levels=expand_passes(
        want.probe_starts, want.probe_offsets, cap))


def _lane(rng, n, ndv, null_share=0.0, dtype=np.int64):
    data = jnp.asarray(rng.integers(0, ndv, n).astype(dtype))
    if not null_share:
        return data, None
    return data, jnp.asarray(rng.random(n) >= null_share)


def _unique(rng):
    nb, npr = 4096, 10_000
    bk = jnp.asarray(rng.permutation(nb).astype(np.int64))
    return [(bk, None)], [_lane(rng, npr, 2 * nb)], np.ones(nb, bool), \
        rng.random(npr) > 0.1, 1 << 14


def _duplicates(rng):
    nb, npr = 2048, 5000
    return [_lane(rng, nb, 40)], [_lane(rng, npr, 60)], rng.random(nb) > 0.2, \
        rng.random(npr) > 0.2, 1 << 18


def _sparse(rng):
    # what a build side gathered out of an upstream join looks like
    nb, npr = 1 << 16, 20_000
    return [_lane(rng, nb, 1 << 30)], [_lane(rng, npr, 1 << 30)], \
        rng.random(nb) < 0.05, np.ones(npr, bool), 1 << 15


def _sparse_matching(rng):
    nb, npr = 1 << 16, 20_000
    return [_lane(rng, nb, 3000)], [_lane(rng, npr, 3000)], \
        rng.random(nb) < 0.05, rng.random(npr) > 0.5, 1 << 16


def _nulls(rng):
    nb, npr = 1024, 3000
    return [_lane(rng, nb, 300, 0.3)], [_lane(rng, npr, 300, 0.3)], \
        rng.random(nb) > 0.1, rng.random(npr) > 0.1, 1 << 15


def _all_dead(rng):
    nb, npr = 512, 700
    return [_lane(rng, nb, 50)], [_lane(rng, npr, 50)], np.zeros(nb, bool), \
        np.ones(npr, bool), 1 << 10


def _hot_key(rng):
    nb, npr = 1000, 64
    return [(jnp.full(nb, 7, jnp.int64), None)], [_lane(rng, npr, 9)], \
        np.ones(nb, bool), np.ones(npr, bool), 1 << 15


def _two_columns(rng):
    nb, npr = 3000, 9000
    return [_lane(rng, nb, 30, 0.05), _lane(rng, nb, 20, dtype=np.int32)], \
        [_lane(rng, npr, 30), _lane(rng, npr, 20, 0.05, dtype=np.int32)], \
        rng.random(nb) > 0.1, rng.random(npr) > 0.1, 1 << 17


def _cap_too_small(rng):
    nb, npr = 128, 128
    return [(jnp.zeros(nb, jnp.int64), None)], [(jnp.zeros(npr, jnp.int64), None)], \
        np.ones(nb, bool), np.ones(npr, bool), 256


def _no_probe_rows(rng):
    return [_lane(rng, 64, 10)], [(jnp.zeros(0, jnp.int64), None)], \
        np.ones(64, bool), np.zeros(0, bool), 32


def _no_build_rows(rng):
    return [(jnp.zeros(0, jnp.int64), None)], [_lane(rng, 64, 10)], \
        np.zeros(0, bool), np.ones(64, bool), 32


def _one_probe_slot(rng):
    return [_lane(rng, 50, 6)], [(jnp.full(1, 3, jnp.int64), None)], \
        rng.random(50) > 0.1, np.ones(1, bool), 64


def _one_slot_a_side(rng):
    one = [(jnp.full(1, 3, jnp.int64), None)]
    return one, one, np.ones(1, bool), np.ones(1, bool), 16


def _one_build_slot(rng):
    return [(jnp.full(1, 3, jnp.int64), None)], [_lane(rng, 50, 6)], \
        np.ones(1, bool), rng.random(50) > 0.1, 64


def _one_dead_build_slot(rng):
    return [(jnp.full(1, 3, jnp.int64), None)], [_lane(rng, 50, 6)], \
        np.zeros(1, bool), np.ones(50, bool), 64


def _fk(rng, nb, probe_keys, plive=None, cap=None):
    """Unique build keys `0..nb-1`: a probe row has one pair or none."""
    pk = np.asarray(probe_keys, np.int64)
    plive = np.ones(pk.shape[0], bool) if plive is None else plive
    return [(jnp.asarray(rng.permutation(nb).astype(np.int64)), None)], \
        [(jnp.asarray(pk), None)], np.ones(nb, bool), plive, cap


def _long_empty_run(rng):
    # two matches, 5,000 probe rows without a pair between them and 3,000 behind
    pk = np.full(8002, 999, np.int64)
    pk[0], pk[5001] = 3, 4
    return _fk(rng, 64, pk, cap=1 << 10)


def _many_pairs_beside_none(rng):
    # rows with 300 pairs, with 2 and with none, next to each other
    nb, npr = 1000, 400
    bk = np.concatenate([np.zeros(300), np.ones(2), np.arange(2, 700)]).astype(np.int64)
    pk = rng.choice(np.array([0, 1, 5000, 6000, 7], np.int64), npr)
    return [(jnp.asarray(bk), None)], [(jnp.asarray(pk), None)], np.ones(nb, bool), \
        rng.random(npr) > 0.1, 1 << 15


def _no_pair_at_all(rng):
    return _fk(rng, 256, rng.integers(1000, 2000, 700), cap=1 << 10)


def _total_is_cap(rng):
    # every live probe row has one pair: 777 pairs in 777 slots
    plive = np.arange(1000) % 9 != 0
    return _fk(rng, 512, rng.integers(0, 512, 1000), plive, cap=int(plive.sum()))


def _quarter_step_cap(rng):
    # 5 x 2^8 slots, as `bucket_capacity` makes them, two thirds full
    return _fk(rng, 512, rng.integers(0, 640, 1000), cap=1280)


def _a_few_probe_rows(rng):
    bk = jnp.asarray(np.repeat(np.arange(8), 5).astype(np.int64))
    return [(bk, None)], [(jnp.asarray(np.array([3, 9, 3, 0, 7], np.int64)), None)], \
        np.ones(40, bool), np.array([1, 1, 0, 1, 1], bool), 64


RATIO_NB = 2048
BUILD_KINDS = {
    # build keys, live build rows, distinct keys a probe row draws from
    "unique": lambda rng: (rng.permutation(RATIO_NB), np.ones(RATIO_NB, bool), 2 * RATIO_NB),
    # runs of 1-7 rows a key, as `l_orderkey`
    "runs_of_1_to_7": lambda rng: (
        np.repeat(np.arange(RATIO_NB), rng.integers(1, 8, RATIO_NB))[:RATIO_NB],
        np.ones(RATIO_NB, bool), RATIO_NB // 2),
    "live_35_percent": lambda rng: (rng.permutation(RATIO_NB),
                                    rng.random(RATIO_NB) < 0.35, 2 * RATIO_NB),
    "one_hot_key": lambda rng: (np.full(RATIO_NB, 7), np.ones(RATIO_NB, bool), 40),
}
# probe slots for one build slot: `npr` = nb / 64, nb / 8, nb, 8 x nb
RATIOS = {"nb_over_64": 1 / 64, "nb_over_8": 1 / 8, "nb": 1, "nb_times_8": 8}


def _ratio_case(kind, ratio):
    def make(rng):
        bk, blive, domain = BUILD_KINDS[kind](rng)
        npr = int(RATIO_NB * RATIOS[ratio])
        return [(jnp.asarray(bk.astype(np.int64)), None)], [_lane(rng, npr, domain)], \
            blive, rng.random(npr) > 0.1, 1 << 16
    return make


CASES = {
    "unique_build_keys": _unique,
    "heavy_duplicates": _duplicates,
    "five_percent_live": _sparse,
    "five_percent_live_matching": _sparse_matching,
    "null_keys_both_sides": _nulls,
    "all_dead_build": _all_dead,
    "one_hot_key": _hot_key,
    "two_column_keys": _two_columns,
    "cap_too_small": _cap_too_small,
    "npr_0": _no_probe_rows,
    "nb_0": _no_build_rows,
    "npr_1": _one_probe_slot,
    "nb_1_npr_1": _one_slot_a_side,
    "nb_1": _one_build_slot,
    "nb_1_dead": _one_dead_build_slot,
    "long_run_of_empty_probe_rows": _long_empty_run,
    "many_pairs_beside_none": _many_pairs_beside_none,
    "total_0": _no_pair_at_all,
    "total_is_cap": _total_is_cap,
    "quarter_step_cap": _quarter_step_cap,
    "npr_5": _a_few_probe_rows,
}
CASES.update({f"{kind}_build_npr_{ratio}": _ratio_case(kind, ratio)
              for kind in BUILD_KINDS for ratio in RATIOS})


def _three_bit_hash(cols):
    return _REAL_HASH(cols) >> np.uint64(61) << np.uint64(61)


_REAL_HASH = K.hash_columns


def _assert_equal_pairs(got: K.JoinPairs, want: K.JoinPairs):
    for name, g, w in zip(K.JoinPairs._fields, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape, name
        assert (g == w).all(), (name, np.nonzero(g != w)[0][:5])


@pytest.mark.parametrize("collide", [False, True], ids=["mix64", "three_bit_hash"])
@pytest.mark.parametrize("case", list(CASES))
def test_every_leaf_equals_the_full_search_oracle(case, collide, monkeypatch):
    if collide:
        # eight distinct hashes: nearly every candidate pair is a collision
        # that `verify` has to drop, and a run of equal hashes holds an eighth
        # of the build side
        monkeypatch.setattr(K, "hash_columns", _three_bit_hash)
    bkeys, pkeys, blive, plive, cap = CASES[case](np.random.default_rng(11))
    if collide:
        cap = max(cap, 1 << 20)
    # a fresh function each time: a trace cached under the other hash must not answer
    got = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, cap))(
        bkeys, pkeys, jnp.asarray(blive), jnp.asarray(plive))
    want = with_expand_passes(oracle(bkeys, pkeys, blive, plive, cap), cap)
    _assert_equal_pairs(got, want)
    assert 0 <= int(got.expand_levels) <= K.full_search_depth(cap - 1)
    if case == "cap_too_small":
        assert bool(got.overflow) != collide  # 16,384 candidates in 256 slots
    if case == "all_dead_build":
        assert not np.asarray(got.live).any()
    if collide:
        return
    total = int(np.asarray(got.probe_offsets)[-1]) if plive.shape[0] else 0
    if case == "total_0":
        assert total == 0 and int(got.expand_levels) == 0
        assert (np.asarray(got.probe_idx) == plive.shape[0] - 1).all()
    if case == "total_is_cap":
        assert total == cap == int(np.asarray(got.live).sum())
        assert not bool(got.overflow)
    if case == "long_run_of_empty_probe_rows":
        assert np.asarray(got.probe_idx)[:3].tolist() == [0, 5001, 8001]
        assert int(got.expand_levels) == 0
    if case == "many_pairs_beside_none":
        assert int(got.expand_levels) == 9  # a row's 300th pair, 299 slots on


def test_verified_pairs_are_the_equal_keys():
    """The oracle shares the hash with the kernel; this one shares nothing."""
    rng = np.random.default_rng(3)
    bkeys, pkeys, blive, plive, cap = _nulls(rng)
    r = K._hash_join_pairs_sorted(bkeys, pkeys, jnp.asarray(blive),
                                  jnp.asarray(plive), cap)
    live = np.asarray(r.live)
    got = sorted(zip(np.asarray(r.build_idx)[live].tolist(),
                     np.asarray(r.probe_idx)[live].tolist()))
    bl, pl = _np_live(bkeys, blive), _np_live(pkeys, plive)
    bd, pd = np.asarray(bkeys[0][0]), np.asarray(pkeys[0][0])
    want = sorted((int(b), int(p)) for p in np.nonzero(pl)[0]
                  for b in np.nonzero(bl & (bd == pd[p]))[0])
    assert got == want and not bool(r.overflow)


def test_dead_sentinel_hash_is_a_live_hash_like_any_other(monkeypatch):
    """A live row whose keys hash to the dead rows' value still finds its
    match, and dead rows are no candidates for it."""
    monkeypatch.setattr(K, "hash_columns",
                        lambda cols: jnp.full(cols[0][0].shape, DEAD, jnp.uint64))
    bk = jnp.asarray(np.arange(32, dtype=np.int64))
    pk = jnp.asarray(np.array([5, 40, 31, 6], np.int64))
    blive = np.arange(32) % 2 == 1
    r = K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], jnp.asarray(blive),
                                  jnp.ones(4, bool), 128)
    assert np.asarray(r.probe_matched).tolist() == [True, False, True, False]
    assert int(np.asarray(r.probe_offsets)[-1]) == 4 * 16  # live rows only


@pytest.mark.parametrize("pairs_a_row,passes", [(1, 0), (2, 1), (5, 3), (64, 6)])
def test_expansion_fills_forward_as_far_as_the_most_pairs_of_a_row(pairs_a_row, passes):
    """None on a key-to-foreign-key join, however many rows lack a pair, and
    `bit_length(pairs - 1)` otherwise: never the depth of a search over the
    probe lane."""
    keys, npr = 4096, 50_000
    rng = np.random.default_rng(31)
    bk = jnp.asarray(rng.permutation(np.repeat(np.arange(keys), pairs_a_row)))
    pk = jnp.asarray(rng.integers(0, 4 * keys, npr))  # three rows in four: no pair
    cap = 1 << 20
    r = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, cap))(
        [(bk, None)], [(pk, None)], jnp.ones(bk.shape[0], bool), jnp.ones(npr, bool))
    assert int(r.expand_levels) == passes < K.full_search_depth(npr) == 16
    assert int(np.asarray(r.live).sum()) == \
        pairs_a_row * int((np.asarray(pk) < keys).sum())


def test_slot_table_formulation_reports_no_depth():
    one = [(jnp.zeros(8, jnp.int64), None)]
    r = K._hash_join_pairs_table(one, one, jnp.ones(8, bool), jnp.ones(8, bool), 128)
    assert r.expand_levels is None


def test_traces_and_agrees_under_shard_map():
    """Each shard joins its own block, with its own trip count: the loops (the
    lookup's running count, the expansion's fill) hold no collective (what
    `parallel/mpp._join_block` relies on)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    shards, nb, npr, cap = 4, 512, 1024, 4096
    rng = np.random.default_rng(23)
    # shard 0: one hot key (a run of 512 build slots); shard 3: an all-dead build side
    bk = rng.integers(0, 200, (shards, nb))
    bk[0] = 7
    pk = rng.integers(0, 200, (shards, npr))
    blive = rng.random((shards, nb)) > 0.2
    blive[0], blive[3] = True, False
    plive = rng.random((shards, npr)) > 0.2

    def block(bk, pk, blive, plive):
        r = K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], blive, plive, cap)
        return r._replace(overflow=r.overflow[None],
                          expand_levels=r.expand_levels[None])

    mesh = Mesh(np.array(jax.devices()[:shards]), ("x",))
    fn = jax.jit(shard_map(block, mesh=mesh, in_specs=(P("x"),) * 4,
                           out_specs=P("x")))
    flat = [jnp.asarray(a.reshape(-1)) for a in (bk, pk, blive, plive)]
    got = fn(*flat)
    for s in range(shards):
        want = with_expand_passes(
            oracle([(bk[s], None)], [(pk[s], None)], blive[s], plive[s], cap), cap)
        mine = K.JoinPairs(*(np.asarray(leaf).reshape(shards, -1)[s].reshape(
            np.shape(w)) for leaf, w in zip(got, want)))
        _assert_equal_pairs(mine, want)
    # the expansion's loop likewise: 512 pairs a row on shard 0, a handful on
    # shards 1 and 2, none on shard 3
    passes = np.asarray(got.expand_levels).tolist()
    assert passes[0] == 9 and 1 <= passes[1] <= 4 and 1 <= passes[2] <= 4
    assert passes[3] == 0


# build rows (two pairs a matching probe row), probe slots a batch: a probe
# side smaller than its build side, and one twice it
OPERATOR_SHAPES = {"probe_700_of_2048": (2000, 700), "probe_1024_of_512": (400, 1024)}


@pytest.mark.parametrize("shape", list(OPERATOR_SHAPES))
@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_operator_counts_its_probes_and_writes_the_expansion_on_its_span(
        join_type, shape, request):
    """`HashJoinOp` on the TPU's formulation: one count per probe batch in
    `JOIN_STATS`, "passes of full depth" of the expansion on the span under
    the cursor, and the rows of the slot-table formulation."""
    from galaxysql_tpu.chunk.batch import Column, ColumnBatch
    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.expr import ir
    from galaxysql_tpu.types import datatype as dt
    from galaxysql_tpu.utils import tracing

    def batch(name, values, live=None):
        col = Column(jnp.asarray(np.asarray(values, np.int64)), None, dt.BIGINT, None)
        return ColumnBatch({name: col}, None if live is None else jnp.asarray(live))

    build_rows, npr = OPERATOR_SHAPES[shape]
    rng = np.random.default_rng(29)
    keys = rng.permutation(3 * build_rows // 2)[:build_rows // 2]
    build = batch("k", np.concatenate([keys, keys]))  # two pairs a matching row
    probes = [batch("a", rng.integers(0, 3 * build_rows // 2, npr), rng.random(npr) > 0.1)
              for _ in range(2)]
    bk, pk = [ir.ColRef("k", dt.BIGINT, None)], [ir.ColRef("a", dt.BIGINT, None)]

    def rows(op):
        out = []
        for b in op.batches():
            cols = sorted(b.columns)
            d = b.compact().to_pydict()
            out += list(zip(*(d[c] for c in cols)))
        return sorted(out, key=str)

    def join():
        return ops.HashJoinOp(ops.SourceOp([build]), ops.SourceOp(probes), bk, pk,
                              join_type, enable_bloom=False,
                              build_schema={"k": (dt.BIGINT, None)})
    before = dict(ops.JOIN_STATS)
    want = rows(join())  # this backend's own formulation, which counts nothing
    assert ops.JOIN_STATS == before

    request.getfixturevalue("chip_formulation")  # from here on, not before
    tc = tracing.TraceContext(7)
    span = tc.add("Join", kind="operator")
    tc.cursor = span.span_id
    op = join()
    with tracing.activate(tc):
        got = rows(op)
    assert got == want and len(want) > 0
    assert ops.JOIN_STATS["probes"] == before["probes"] + 2
    tree = "\n".join(tc.tree_lines())
    if join_type in ("semi", "anti"):
        # no residual: which probe rows match is asked of the ranges
        # themselves, no pair is enumerated and nothing is expanded
        assert ops.JOIN_STATS["expand_levels"] == before["expand_levels"]
        assert "expand_levels" not in span.attrs
        return
    # the expansion: one pass a probe carries a row's id to its second pair,
    # where a search over the probe slots would run ten or eleven levels
    expand_full = K.full_search_depth(npr)
    assert ops.JOIN_STATS["expand_levels"] == before["expand_levels"] + 2
    assert ops.JOIN_STATS["expand_full_depth_levels"] == \
        before["expand_full_depth_levels"] + 2 * expand_full
    assert span.attrs["expand_levels"] == f"2 of {2 * expand_full}"
    assert f"expand_levels=2 of {2 * expand_full}" in tree


TOP = DEAD - np.uint64(1)  # what a live hash is held to


def _hashes_of(kind, ratio):
    def make(rng):
        bkeys, pkeys, blive, _plive, _cap = _ratio_case(kind, ratio)(rng)
        h_b = np.minimum(np.asarray(K.hash_columns(bkeys)), TOP)
        return np.where(blive, h_b, DEAD), np.minimum(np.asarray(K.hash_columns(pkeys)), TOP)
    return make


def _u64(*values):
    return np.array(values, np.uint64)


def _joined(*parts):
    return np.concatenate([np.asarray(part).astype(np.uint64) for part in parts])


# the two lanes the lookup is handed: build hashes as they come (dead rows hold
# `DEAD`), probe hashes (a dead probe row's is looked up like any other: its
# count is dropped after)
HASH_LANES = {f"{kind}_build_npr_{ratio}": _hashes_of(kind, ratio)
              for kind in BUILD_KINDS for ratio in RATIOS}
HASH_LANES.update({
    "nb_1_npr_1_match": lambda rng: (_u64(77), _u64(77)),
    "nb_1_npr_1_no_match": lambda rng: (_u64(77), _u64(78)),
    "nb_1_dead": lambda rng: (_u64(DEAD), _u64(5, TOP, 0)),
    "all_dead_build": lambda rng: (np.full(300, DEAD), rng.integers(0, 1 << 62, 500).astype(np.uint64)),
    # live rows AT the top live value, beside dead rows one above it
    "top_live_value": lambda rng: (
        rng.permutation(_joined(np.full(5, TOP), np.full(40, DEAD), _u64(3, 3, TOP - np.uint64(1)))),
        _u64(TOP, TOP - np.uint64(1), TOP - np.uint64(2), 3, TOP, 9)),
    # hashes equal in their upper 63 bits are one hash to the lookup (its lane
    # keeps the lowest bit for the side)
    "differ_in_the_lowest_bit": lambda rng: (
        rng.permutation(_u64(*[2 * x + b for x in range(100, 164) for b in (0, 0, 1)])),
        _u64(*[2 * x + b for x in range(98, 168) for b in (1, 0)])),
    "probes_below_and_above_every_build_hash": lambda rng: (
        rng.integers(1 << 20, 1 << 21, 64).astype(np.uint64),
        _joined(np.zeros(9, np.uint64), np.full(9, TOP), rng.integers(1 << 20, 1 << 21, 30))),
    "one_hot_key_between_two_keys": lambda rng: (
        rng.permutation(_joined(np.full(5000, 50), _u64(49, 51), np.full(70, DEAD))),
        rng.integers(48, 53, 4000).astype(np.uint64)),
})


def _assert_searchsorteds(h_b, h_p):
    left, run = jax.jit(K._merge_ranges)(jnp.asarray(h_b), jnp.asarray(h_p))
    want_left, want_right = _searchsorteds(np.sort(h_b), h_p)
    assert (np.asarray(left) == want_left).all()
    assert (np.asarray(run) == want_right - want_left).all()
    assert left.dtype == run.dtype == jnp.int32
    return np.asarray(run)


@pytest.mark.parametrize("lanes", list(HASH_LANES))
def test_left_and_run_are_searchsorteds(lanes):
    """The lookup alone over the build hashes (dead rows behind the live ones
    once sorted) and the probe hashes: `left` and `run` bit for bit what
    `np.searchsorted` left and right give over the upper 63 bits of the live
    rows' sorted hashes, with no search at all."""
    h_b, h_p = HASH_LANES[lanes](np.random.default_rng(37))
    run = _assert_searchsorteds(h_b, h_p)
    if "_build_npr_" in lanes or lanes in ("top_live_value", "differ_in_the_lowest_bit"):
        assert bool((run > 0).any()) and bool((run == 0).any())
    if lanes == "top_live_value":  # never a dead row; TOP - 2 shares 63 bits with TOP - 1
        assert run.tolist() == [5, 1, 1, 2, 5, 0]
    if lanes == "differ_in_the_lowest_bit":
        assert sorted(set(run.tolist())) == [0, 3]
    if lanes == "one_hot_key_between_two_keys":
        assert dict(zip(h_p.tolist(), run.tolist())) == \
            {48: 1, 49: 1, 50: 5001, 51: 5001, 52: 0}


# build x probe slots of every sorted probe the benchmark's cells make at SF1
# (listed on this CPU under the chip's formulations; PERF.md sections 4-5)
CELL_SHAPES = {
    # `tpch_sf1.join`'s seven probes: every probe side the larger one
    "q3_customer_orders": (32_768, 1_572_864),
    "q3_orders_lineitem": (163_840, 6_291_456),
    "q5_customer_orders": (163_840, 1_572_864),
    "q5_region_nation": (1_024, 1_024),
    "q5_nation_supplier": (1_024, 16_384),
    "q5_supplier_lineitem": (4_096, 6_291_456),
    "q5_orders_lineitem": (229_376, 458_752),
    # `tpch_sf1_joinkinds.left_anti_semi`: build sides ten to a hundred times
    # their probe sides
    "q13_left": (1_572_864, 163_840),
    "q22_anti": (1_572_864, 32_768),
    "q4_semi_one_chip": (6_291_456, 65_536),
    # `tpch_sf1_mpp4_subq.semi_anti`, slots a shard
    "q21_semi": (4_194_304, 65_536),
    "q21_anti": (2_097_152, 131_072),
    "q4_semi": (2_097_152, 32_768),
    "orders_broadcast": (2_097_152, 65_536),
    "supplier_broadcast": (4_096, 1_048_576),
    "nation_broadcast": (512, 1_024),
    # `tpch_sf1_mpp4.join_q3`: `customer` gathered into `orders`, then that
    # result shuffled into `lineitem`
    "join_q3_broadcast": (32_768, 65_536),
    "join_q3_shuffle": (131_072, 32_768),
    # `tpch_sf1.scan` joins nothing
}


@pytest.mark.parametrize("join", list(CELL_SHAPES))
def test_left_and_run_are_searchsorteds_at_the_cells_ratios(join):
    """One lookup whatever the two shapes: the cells' build and probe slots over
    256 (one slot at least), build keys in runs of 1-7 and a third of the build
    rows dead, probe keys from twice the build side's domain."""
    nb, npr = (max(n // 256, 1) for n in CELL_SHAPES[join])
    rng = np.random.default_rng(nb + npr)
    bk = np.repeat(np.arange(nb), rng.integers(1, 8, nb))[:nb].astype(np.int64)
    h_b = np.minimum(np.asarray(K.hash_columns([(jnp.asarray(bk), None)])), TOP)
    h_b = np.where(rng.random(nb) < 0.67, h_b, DEAD)
    pk = rng.integers(0, 2 * int(bk.max()) + 2, npr)
    h_p = np.minimum(np.asarray(K.hash_columns([(jnp.asarray(pk), None)])), TOP)
    run = _assert_searchsorteds(h_b, h_p)
    if npr >= 64:
        assert bool((run > 0).any()) and bool((run == 0).any())


def _collide_in_63_bits(cols):
    """Keys 2x and 2x + 1 hash to values that differ in the lowest bit only."""
    (data, _), = cols
    return (_REAL_HASH([(data >> 1, None)]) & ~np.uint64(1)) | (data & 1).astype(jnp.uint64)


def test_keys_whose_hashes_differ_in_the_lowest_bit_join_exactly(monkeypatch):
    """Neighbouring keys collide in 63 bits of their hash and differ in the
    64th: the lookup hands both to `verify` (and to the pair-less probe's
    comparison in place), which tells them apart; and with the bit dropped
    from the hash too: the pairs are the equal keys."""
    for hash_ in (_collide_in_63_bits,
                  lambda cols: _collide_in_63_bits(cols) & ~np.uint64(1)):
        monkeypatch.setattr(K, "hash_columns", hash_)
        rng = np.random.default_rng(41)
        bd, pd = rng.integers(0, 400, 700), rng.integers(0, 400, 900)
        r = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, 1 << 13))(
            [(jnp.asarray(bd), None)], [(jnp.asarray(pd), None)],
            jnp.ones(700, bool), jnp.ones(900, bool))
        live = np.asarray(r.live)
        got = sorted(zip(np.asarray(r.build_idx)[live].tolist(),
                         np.asarray(r.probe_idx)[live].tolist()))
        assert got == sorted((int(b), int(p)) for p in range(900)
                             for b in np.nonzero(bd == pd[p])[0])
        assert not bool(r.overflow)
        matched = jax.jit(lambda *a: K.hash_join_matched(*a))(
            [(jnp.asarray(bd), None)], [(jnp.asarray(pd), None)],
            jnp.ones(700, bool), jnp.ones(900, bool))
        assert (np.asarray(matched) == np.isin(pd, bd)).all()


# build slots, probe slots: a probe side smaller, equal, larger, and far apart
SIDE_SHAPES = [(1000, 999), (1000, 1000), (1000, 1001), (64, 4096), (4096, 64), (1, 1)]


@pytest.mark.parametrize("nb,npr", SIDE_SHAPES)
def test_pairs_and_the_pairless_probe_agree_whichever_side_is_larger(nb, npr):
    """`_hash_join_pairs_sorted` against the oracle, and `hash_join_matched`,
    whole and through a front bucket that holds the live probe rows, against
    the pairs' `probe_matched`."""
    rng = np.random.default_rng(nb + npr)
    bkeys, pkeys = [_lane(rng, nb, 600, 0.1)], [_lane(rng, npr, 900, 0.1)]
    blive, plive = jnp.asarray(rng.random(nb) > 0.2), jnp.asarray(rng.random(npr) > 0.5)
    cap = 1 << 14
    slots = max(int(np.asarray(_np_live(pkeys, plive)).sum()), 1)
    pairs = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, cap))(
        bkeys, pkeys, blive, plive)
    whole = jax.jit(lambda *a: K.hash_join_matched(*a))(bkeys, pkeys, blive, plive)
    front = jax.jit(lambda *a: K.hash_join_matched(*a, slots))(bkeys, pkeys, blive, plive)
    want = with_expand_passes(oracle(bkeys, pkeys, np.asarray(blive), np.asarray(plive),
                                     cap), cap)
    _assert_equal_pairs(pairs, want)
    assert whole.shape == front.shape == (npr,)
    assert (np.asarray(whole) == np.asarray(want.probe_matched)).all()
    assert (np.asarray(front) == np.asarray(want.probe_matched)).all()


@pytest.mark.parametrize("exchange", ["broadcast", "shuffle"])
def test_a_mesh_join_looks_up_the_slots_a_shard_joins(exchange, monkeypatch,
                                                      chip_formulation):
    """A semi join through `MppExecutor` on four virtual devices, a fact
    table on its build side and then on its probe side: the one lookup is
    traced under `shard_map` with the slots a shard joins (what the stage's
    span says of its quotas), whichever side is the larger, and the counts are
    exact.  The slot-table formulation looks no range up."""
    from galaxysql_tpu.parallel import mpp as M
    from galaxysql_tpu.parallel.mesh import make_mesh
    from galaxysql_tpu.plan.physical import ExecContext
    from galaxysql_tpu.server.instance import Instance
    from galaxysql_tpu.server.session import Session
    from galaxysql_tpu.utils import tracing

    shards = 4
    inst = Instance()
    inst._mesh = make_mesh(shards)
    s = Session(inst)
    s.execute("CREATE DATABASE d")
    s.execute("USE d")
    for table in ("fact", "few"):
        s.execute(f"CREATE TABLE {table} (id BIGINT NOT NULL PRIMARY KEY, "
                  f"k BIGINT NOT NULL) PARTITION BY HASH(id) PARTITIONS 8")
    rng = np.random.default_rng(5)
    fact = {"id": np.arange(20_000), "k": rng.integers(0, 5000, 20_000)}
    few = {"id": np.arange(300), "k": rng.integers(0, 10_000, 300)}
    inst.store("d", "fact").insert_arrays(fact, inst.tso.next_timestamp())
    inst.store("d", "few").insert_arrays(few, inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE fact, few")
    if exchange == "shuffle":
        monkeypatch.setattr(M, "BROADCAST_BUILD_LIMIT", 0)

    traced = []  # (build slots, probe slots) of every range lookup traced
    merge = K._merge_ranges
    monkeypatch.setattr(K, "_merge_ranges", lambda h_b, h_p: (
        traced.append((h_b.shape[0], h_p.shape[0])), merge(h_b, h_p))[1])

    def run(outer, inner):
        inst.frag_cache.clear()
        plan = inst.planner.plan_select(
            f"SELECT COUNT(*) FROM {outer} WHERE EXISTS "
            f"(SELECT * FROM {inner} WHERE {inner}.k = {outer}.k)", "d")
        ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                          archive=inst.archive, archive_instance=inst)
        tc = tracing.TraceContext(32, node="t")
        del traced[:]
        with tracing.activate(tc):
            batch = M.MppExecutor(ctx, make_mesh(shards)).execute(plan.rel)
        join, = [sp for sp in tc.spans if sp.kind == "stage" and sp.name == "mpp:Join"]
        assert join.attrs["exchange"] == exchange and join.attrs["kind"] == "semi"
        return batch.to_pylist()[0][0], join.attrs

    def slots_of(attrs, nb, npr):
        if exchange == "shuffle":
            assert (nb, npr) == (shards * attrs["quota_b"], shards * attrs["quota_p"])
        else:
            assert nb == attrs["build_slots"]

    try:
        count, attrs = run("few", "fact")
        assert count == int(np.isin(few["k"], fact["k"]).sum())
        (nb, npr), = set(traced)
        slots_of(attrs, nb, npr)
        assert nb >= 16 * npr

        count, attrs = run("fact", "few")  # the fact table probes
        assert count == int(np.isin(fact["k"], few["k"]).sum())
        (nb, npr), = set(traced)
        slots_of(attrs, nb, npr)
        assert npr >= nb

        monkeypatch.setattr(K, "prefer_scatter", lambda: True)
        from galaxysql_tpu.exec import operators as ops
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.clear()  # keyed alike under both formulations
        count, attrs = run("few", "fact")
        assert count == int(np.isin(few["k"], fact["k"]).sum())
        assert not traced
    finally:
        s.close()


def _located(text):
    """Every `stablehlo` operation of a lowered module's text as `(name, its
    line, its location spelled out)`: `loc(#loc7)` is followed through the
    aliases at the text's end, and an operation that holds a region (`while`,
    `scatter`, `reduce_window`) has its types and its location where the region
    closes, so its line is the first and the last."""
    alias = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def spell(ref, depth=0):
        body = alias.get(ref, "")
        return body if depth > 20 else re.sub(
            r"#loc\d+", lambda m: spell(m.group(0), depth + 1), body)

    open_ = []  # operations (or functions: None) whose region is still open
    for line in text.splitlines():
        op = re.search(r"stablehlo\.\w+", line)
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        closes = re.match(r"\s*\}", line)
        if closes and at and open_:
            started = open_.pop()
            if started is not None:
                yield started[0], started[1] + line, spell(at.group(1))
        elif op and at and not closes:
            yield op.group(0), line, spell(at.group(1))
        elif op and "stablehlo.while(" in line:  # `cond {`, `} do {`, `} loc(..)` follow
            open_.append((op.group(0), line))
        elif not closes and line.rstrip().endswith("{") and line.strip() != "cond {":
            open_.append((op.group(0), line) if op else None)


def _lowered_for_a_tpu(nb, npr, cap, debug_info=True, matched=False):
    """The sorted join (or the pair-less probe) over one int64 key a side, as
    the chip's compiler is handed it: lowered for a TPU here, no chip."""
    def run(bk, pk, blive, plive):
        if matched:
            return K.hash_join_matched([(bk, None)], [(pk, None)], blive, plive)
        return K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], blive, plive, cap)

    shapes = [jax.ShapeDtypeStruct((n,), t) for n, t in
              ((nb, jnp.int64), (npr, jnp.int64), (nb, jnp.bool_), (npr, jnp.bool_))]
    return jax.jit(run).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=debug_info)


def test_expand_scope_gathers_no_64_bit_lane_and_nothing_runs_a_window():
    """What the chip's compiler is handed (lowered for a TPU here, no chip):
    inside `join_pairs/expand` no gather reads a 64-bit lane (two words a
    gathered element on the chip), and no running maximum or minimum came back
    as a reduce-window (33-40 s of compile a program, PERF.md PR 26): the only
    windows are `jnp.cumsum`'s running sums: the lookup's count of build slots
    along the merged lane, the pair offsets, `probe_matched_from`'s."""
    ops_ = list(_located(_lowered_for_a_tpu(256, 1000, 640)))
    in_expand = [(op, line) for op, line, where in ops_ if "join_pairs/expand" in where]
    assert {"stablehlo.scatter", "stablehlo.gather", "stablehlo.while"} <= \
        {op for op, _ in in_expand}  # the scope is found, and is the expansion
    gathers = [line for op, line in in_expand if op == "stablehlo.gather"]
    assert len(gathers) == 2
    for line in gathers:
        operand = re.search(r": \(tensor<\d+x(\w+)>", line).group(1)
        assert operand in ("i32", "ui32"), line
    assert not [line for op, line in in_expand if op == "stablehlo.scatter"
                and not re.search(r"\}\) : \(tensor<\d+xi32>", line)]
    windows = [where for op, _, where in ops_ if op == "stablehlo.reduce_window"]
    assert len(windows) == 3, windows
    assert all("reduce_window_sum" in w for w in windows), windows


@pytest.mark.parametrize("matched", [False, True], ids=["pairs", "matched"])
@pytest.mark.parametrize("nb,npr", [(4096, 65_536), (65_536, 4096)],
                         ids=["probe_larger", "build_larger"])
def test_probe_scope_sorts_twice_and_gathers_nothing(nb, npr, matched):
    """Lowered for a TPU here, no chip, whichever side is the larger:
    `join_pairs/probe` holds two sorts (the merged lane of 69,632 slots by
    hash with its ids, and the sort back by id), the loop of the running
    count, and NO gather or scatter, where a search gathers words by the probe
    slot; `join_pairs/sort` holds the build side's `argsort` and gathers no
    sorted hash back."""
    ops_ = list(_located(_lowered_for_a_tpu(nb, npr, 1 << 17, matched=matched)))
    in_probe = [(op, line) for op, line, where in ops_ if "join_pairs/probe" in where]
    names = [op for op, _ in in_probe]
    assert "stablehlo.gather" not in names and "stablehlo.scatter" not in names
    assert "stablehlo.while" in names
    sorts = [line for op, line in in_probe if op == "stablehlo.sort"]
    assert len(sorts) == 2 and all(f"tensor<{nb + npr}x" in line for line in sorts)
    assert all("is_stable = false" in line for line in sorts), sorts
    assert "tensor<69632xui64>" in sorts[0] and "ui64" not in sorts[1]
    # a third sort in the program, `argsort`'s (a function of its own in the
    # text), whose lane is not gathered back
    assert [op for op, _, _ in ops_].count("stablehlo.sort") == 3
    assert "stablehlo.gather" not in [op for op, _, where in ops_
                                      if "join_pairs/sort" in where]
