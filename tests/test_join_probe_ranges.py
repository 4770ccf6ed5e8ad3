"""The TPU join's range lookup (`K._probe_ranges`: prefix directory, bounded
search, run lengths) against a NumPy oracle that searches the whole sorted
build lane twice, as the formulation did before: every leaf of `JoinPairs` equal,
the depth it reports, and the same function under `shard_map`; and its ragged
expansion (`K._expand_rows`: one scatter, a running maximum by doubling
strides) against the same oracle's full-depth search for every pair slot; the
directory's width (`K.directory_bits`, from both sides' shapes) alone, in the
lowered text, and as `dir_bits=` on the spans of a join.

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
                           ends, ends, np.bool_(False), np.int32(0))
    top = DEAD - np.uint64(1)
    h_b = np.where(b_live, np.minimum(np.asarray(K.hash_columns(build_keys)), top), DEAD)
    h_p = np.minimum(np.asarray(K.hash_columns(probe_keys)), top)
    perm = np.argsort(h_b, kind="stable")
    h_sorted = h_b[perm]
    left = np.searchsorted(h_sorted, h_p, side="left")
    right = np.searchsorted(h_sorted, h_p, side="right")
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
    widest = _widest_bucket(h_sorted, nb, npr)
    return K.JoinPairs(b_of, p_of, verified, matched, starts, offsets,
                       np.bool_(total > cap), np.int32(int(widest).bit_length()))


def _widest_bucket(h_sorted, nb, npr):
    """Most live hashes that share the top bits the directory indexes: the
    smaller of `bit_length(nb) - 4` and `bit_length(npr) - 4` (one bit at
    least)."""
    live = h_sorted[h_sorted != DEAD]
    k_bits = max(min(int(nb).bit_length(), int(npr).bit_length()) - 4, 1)
    if not live.size:
        return 0
    return np.bincount((live >> np.uint64(64 - k_bits)).astype(np.int64)).max()


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
        # that `verify` has to drop, and the widest bucket holds an eighth of
        # the build side
        monkeypatch.setattr(K, "hash_columns", _three_bit_hash)
    bkeys, pkeys, blive, plive, cap = CASES[case](np.random.default_rng(11))
    if collide:
        cap = max(cap, 1 << 20)
    # a fresh function each time: a trace cached under the other hash must not answer
    got = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, cap))(
        bkeys, pkeys, jnp.asarray(blive), jnp.asarray(plive))
    want = with_expand_passes(oracle(bkeys, pkeys, blive, plive, cap), cap)
    _assert_equal_pairs(got, want)
    nb = blive.shape[0]
    assert 0 <= int(got.search_levels) <= K.full_search_depth(nb)
    assert 0 <= int(got.expand_levels) <= K.full_search_depth(cap - 1)
    if case == "cap_too_small":
        assert bool(got.overflow) != collide  # 16,384 candidates in 256 slots
    if case == "one_hot_key":
        assert int(got.search_levels) == K.full_search_depth(nb) == 10
    if case == "all_dead_build":
        assert int(got.search_levels) == 0 and not np.asarray(got.live).any()
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
    assert int(r.search_levels) == 5


def test_uniform_keys_search_a_few_levels_of_the_full_depth():
    nb, npr = 65_536, 100_000
    rng = np.random.default_rng(17)
    bk = jnp.asarray(rng.permutation(nb).astype(np.int64))
    pk = jnp.asarray(rng.integers(0, nb, npr))
    r = jax.jit(lambda *a: K._hash_join_pairs_sorted(*a, 1 << 17))(
        [(bk, None)], [(pk, None)], jnp.ones(nb, bool), jnp.ones(npr, bool))
    assert K.full_search_depth(nb) == 17
    assert 1 <= int(r.search_levels) <= 8
    assert int(np.asarray(r.live).sum()) == npr and not bool(r.overflow)


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
    assert r.search_levels is None and r.expand_levels is None


def test_traces_and_agrees_under_shard_map():
    """Each shard joins its own block, with its own trip count: the loop holds
    no collective (what `parallel/mpp._join_block` relies on)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    shards, nb, npr, cap = 4, 512, 1024, 4096
    rng = np.random.default_rng(23)
    # shard 0: one hot key (full depth); shard 3: an all-dead build side
    bk = rng.integers(0, 200, (shards, nb))
    bk[0] = 7
    pk = rng.integers(0, 200, (shards, npr))
    blive = rng.random((shards, nb)) > 0.2
    blive[0], blive[3] = True, False
    plive = rng.random((shards, npr)) > 0.2

    def block(bk, pk, blive, plive):
        r = K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], blive, plive, cap)
        return r._replace(overflow=r.overflow[None],
                          search_levels=r.search_levels[None],
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
    levels = np.asarray(got.search_levels).tolist()
    assert levels[0] == K.full_search_depth(nb) and levels[3] == 0
    # the expansion's loop likewise: 512 pairs a row on shard 0, a handful on
    # shards 1 and 2, none on shard 3
    passes = np.asarray(got.expand_levels).tolist()
    assert passes[0] == 9 and 1 <= passes[1] <= 4 and 1 <= passes[2] <= 4
    assert passes[3] == 0


@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_operator_counts_the_depth_and_writes_it_on_its_span(join_type, request):
    """`HashJoinOp` on the TPU's formulation: one count per probe batch in
    `JOIN_STATS`, "levels of full depth" on the span under the cursor for the
    range search and for the expansion, and the rows of the slot-table
    formulation."""
    from galaxysql_tpu.chunk.batch import Column, ColumnBatch
    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.expr import ir
    from galaxysql_tpu.types import datatype as dt
    from galaxysql_tpu.utils import tracing

    def batch(name, values, live=None):
        col = Column(jnp.asarray(np.asarray(values, np.int64)), None, dt.BIGINT, None)
        return ColumnBatch({name: col}, None if live is None else jnp.asarray(live))

    rng = np.random.default_rng(29)
    keys = rng.permutation(3000)[:1000]
    build = batch("k", np.concatenate([keys, keys]))  # two pairs a matching row
    probes = [batch("a", rng.integers(0, 3000, 700), rng.random(700) > 0.1)
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
    full = K.full_search_depth(ops.bucket_capacity(2000))
    assert ops.JOIN_STATS["probes"] == before["probes"] + 2
    assert ops.JOIN_STATS["full_depth_levels"] == before["full_depth_levels"] + 2 * full
    levels = ops.JOIN_STATS["search_levels"] - before["search_levels"]
    assert 2 <= levels <= 2 * 8 < 2 * full
    assert span.attrs["search_levels"] == f"{levels} of {2 * full}"
    assert f"search_levels={levels} of {2 * full}" in "\n".join(tc.tree_lines())
    if join_type in ("semi", "anti"):
        # no residual: which probe rows match is asked of the ranges
        # themselves, no pair is enumerated and nothing is expanded
        assert ops.JOIN_STATS["expand_levels"] == before["expand_levels"]
        assert "expand_levels" not in span.attrs
        assert span.attrs["dir_bits"] == "6 of 8"
        return
    # the expansion: one pass a probe carries a row's id to its second pair,
    # where a search over the 700 probe slots would run ten levels
    expand_full = K.full_search_depth(700)
    assert ops.JOIN_STATS["expand_levels"] == before["expand_levels"] + 2
    assert ops.JOIN_STATS["expand_full_depth_levels"] == \
        before["expand_full_depth_levels"] + 2 * expand_full
    assert span.attrs["expand_levels"] == f"2 of {2 * expand_full}"
    assert f"expand_levels=2 of {2 * expand_full}" in "\n".join(tc.tree_lines())
    # the directory's width, from the shapes alone: 2,048 build slots probed
    # by 700 take six bits of the eight the build side alone would give
    assert span.attrs["dir_bits"] == K.directory_bits_note(
        ops.bucket_capacity(2000), 700) == "6 of 8"
    assert "dir_bits=6 of 8" in "\n".join(tc.tree_lines())


@pytest.mark.parametrize("ratio", list(RATIOS))
@pytest.mark.parametrize("kind", list(BUILD_KINDS))
def test_left_and_run_are_searchsorteds(kind, ratio):
    """`_probe_ranges` alone over a sorted lane of hashes, dead rows behind the
    live ones: `left` and `run` bit for bit what two searches of the whole
    lane give, at every width the two shapes choose."""
    bkeys, pkeys, blive, _plive, _cap = _ratio_case(kind, ratio)(np.random.default_rng(37))
    npr = pkeys[0][0].shape[0]
    h_b = np.minimum(np.asarray(K.hash_columns(bkeys)), DEAD - np.uint64(1))
    h_sorted = jnp.asarray(np.sort(np.where(blive, h_b, DEAD)))
    h_p = jnp.minimum(K.hash_columns(pkeys), DEAD - np.uint64(1))
    left, run, levels = jax.jit(K._probe_ranges)(h_sorted, h_p)
    want_left = jnp.searchsorted(h_sorted, h_p, side="left")
    assert (np.asarray(left) == np.asarray(want_left)).all()
    assert (np.asarray(run) == np.asarray(
        jnp.searchsorted(h_sorted, h_p, side="right") - want_left)).all()
    assert int(levels) == int(_widest_bucket(np.asarray(h_sorted), RATIO_NB,
                                             npr)).bit_length()
    assert bool((np.asarray(run) > 0).any()) and bool((np.asarray(run) == 0).any())


def _bucket_capacities():
    from galaxysql_tpu.exec.operators import bucket_capacity
    return sorted({bucket_capacity(n) for e in range(0, 25)
                   for n in (1 << e, 5 << e >> 2, 3 << e >> 1, 7 << e >> 2)})


def test_directory_bits_are_the_build_sides_own_where_the_probe_is_no_smaller():
    """What keeps every program whose probe side is the larger one as it was:
    over the capacities a batch can have, `npr >= nb` gives
    `bit_length(nb) - 4` (one bit at least)."""
    caps = _bucket_capacities()
    assert len(caps) >= 40 and caps[-1] >= 1 << 24  # powers of two, then quarter steps
    for nb in caps + [1, 2, 3, 1000, 4097]:
        today = max(nb.bit_length() - 4, 1)
        for npr in [n for n in caps if n >= nb] + [nb, nb + 1, 8 * nb]:
            assert K.directory_bits(nb, npr) == today, (nb, npr)
        assert K.directory_bits_note(nb, nb) == f"{today} of {today}"


def test_directory_bits_never_fall_below_one_and_never_fall_as_the_probe_grows():
    caps = _bucket_capacities()
    for nb in caps:
        widths = [K.directory_bits(nb, npr) for npr in [0, 1, 2, 15, 16, 17] + caps]
        assert widths[0] == 1 and min(widths) >= 1
        assert widths == sorted(widths), nb
        assert widths[-1] == max(nb.bit_length() - 4, 1) == max(widths)


@pytest.mark.parametrize("join,nb,npr,now,before", [
    # `tpch_sf1_mpp4_subq.semi_anti`'s five joins, slots a shard (PERF.md section 5)
    ("q21_semi", 4_194_304, 65_536, 13, 19),
    ("q21_anti", 2_097_152, 131_072, 14, 18),
    ("q4_semi", 2_097_152, 32_768, 12, 18),
    ("orders_broadcast", 2_097_152, 65_536, 13, 18),
    ("supplier_broadcast", 4_096, 1_048_576, 9, 9),
    # `tpch_sf1_mpp4.join_q3`'s shuffle join
    ("join_q3_shuffle", 131_072, 32_768, 12, 14),
])
def test_directory_bits_at_the_mesh_cells_shapes(join, nb, npr, now, before):
    assert K.directory_bits(nb, npr) == now
    assert K.directory_bits_note(nb, npr) == f"{now} of {before}"
    # the model the width comes from, in gathered 32-bit words: the width
    # chosen costs no more than the build side's own, and is within a fifth
    # of the best width there is
    depth = nb.bit_length()

    def words(k):
        return 2 * depth * ((1 << k) + 1) + npr * (2 + 2 * (depth - k + 2) + 3)
    assert words(now) <= words(before)
    assert words(now) <= 1.2 * min(words(k) for k in range(1, before + 1))


@pytest.mark.parametrize("exchange", ["broadcast", "shuffle"])
def test_stage_span_carries_the_directory_bits_its_program_was_built_with(
        exchange, monkeypatch, chip_formulation):
    """A semi join through `MppExecutor` on four virtual devices, a fact
    table on its build side: `dir_bits=` on `stage:Join` is the width the
    traced kernel chose from the slots a shard joined, a narrowed directory
    here; with the sides swapped, the build side's own.  The slot-table
    formulation builds no directory and says nothing."""
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
    real = K._probe_ranges
    monkeypatch.setattr(K, "_probe_ranges", lambda h_sorted, h_p: (
        traced.append((h_sorted.shape[0], h_p.shape[0])), real(h_sorted, h_p))[1])

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
        return batch.to_pylist()[0][0], join.attrs, "\n".join(tc.tree_lines())

    try:
        count, attrs, tree = run("few", "fact")
        assert count == int(np.isin(few["k"], fact["k"]).sum())
        (nb, npr), = set(traced)
        if exchange == "shuffle":
            assert (nb, npr) == (shards * attrs["quota_b"], shards * attrs["quota_p"])
        else:
            assert nb == attrs["build_slots"]
        assert nb >= 16 * npr
        assert attrs["dir_bits"] == K.directory_bits_note(nb, npr)
        narrowed, full = (int(n) for n in attrs["dir_bits"].split(" of "))
        assert 1 <= narrowed == npr.bit_length() - 4 < full == nb.bit_length() - 4
        assert f"dir_bits={narrowed} of {full}" in tree

        count, attrs, _ = run("fact", "few")  # the fact table probes
        assert count == int(np.isin(fact["k"], few["k"]).sum())
        (nb, npr), = set(traced)
        assert npr >= nb
        assert attrs["dir_bits"] == f"{nb.bit_length() - 4} of {nb.bit_length() - 4}"

        monkeypatch.setattr(K, "prefer_scatter", lambda: True)
        from galaxysql_tpu.exec import operators as ops
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.clear()  # keyed alike under both formulations
        count, attrs, _ = run("few", "fact")
        assert count == int(np.isin(few["k"], fact["k"]).sum())
        assert "dir_bits" not in attrs and not traced
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


def test_expand_scope_gathers_no_64_bit_lane_and_nothing_runs_a_window():
    """What the chip's compiler is handed (lowered for a TPU here, no chip):
    inside `join_pairs/expand` no gather reads a 64-bit lane (two words a
    gathered element on the chip), and no running maximum or minimum came back
    as a reduce-window (33-40 s of compile a program, PERF.md PR 26): the only
    windows are `jnp.cumsum`'s two running sums, which stood before."""
    def run(bk, pk, blive, plive):
        return K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], blive, plive, 640)

    shapes = [jax.ShapeDtypeStruct((n,), t) for n, t in
              ((256, jnp.int64), (1000, jnp.int64), (256, jnp.bool_), (1000, jnp.bool_))]
    text = jax.jit(run).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    ops_ = list(_located(text))
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
    assert len(windows) == 2 and all("reduce_window_sum" in w for w in windows), windows


def test_probe_scope_builds_the_narrow_directory_where_the_probe_is_small():
    """Lowered for a TPU here, no chip: 1,048,576 build slots probed by 4,096.
    The build side alone would take 17 bits, a directory of 131,073 full-depth
    searches; the probe side's 9 make it 513, and nothing of 2^16 + 1 or
    2^17 + 1 elements is left in `join_pairs/probe`.  With the sides swapped
    the directory is the build side's own, as it was."""
    def lowered(nb, npr):
        def run(bk, pk, blive, plive):
            return K._hash_join_pairs_sorted([(bk, None)], [(pk, None)], blive,
                                             plive, 8192)
        shapes = [jax.ShapeDtypeStruct((n,), t) for n, t in
                  ((nb, jnp.int64), (npr, jnp.int64), (nb, jnp.bool_), (npr, jnp.bool_))]
        text = jax.jit(run).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        return [(op, line) for op, line, where in _located(text)
                if "join_pairs/probe" in where]

    def lanes_of(in_probe, elements):
        return [line for _, line in in_probe if f"tensor<{elements}x" in line]

    in_probe = lowered(1 << 20, 1 << 12)
    assert {"stablehlo.while", "stablehlo.gather"} <= {op for op, _ in in_probe}
    assert K.directory_bits(1 << 20, 1 << 12) == 9
    assert lanes_of(in_probe, (1 << 9) + 1)
    assert not lanes_of(in_probe, (1 << 17) + 1) and not lanes_of(in_probe, (1 << 16) + 1)

    swapped = lowered(1 << 12, 1 << 20)
    assert K.directory_bits(1 << 12, 1 << 20) == 9
    assert lanes_of(swapped, (1 << 9) + 1)
