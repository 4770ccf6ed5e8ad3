"""Core relational kernels over fixed-shape device arrays.

These replace the reference's operator hot loops (SURVEY.md §3.3: hash-table build/probe in
`ParallelHashJoinExec.java:131-226`, agg-map updates in `AggOpenHashMap`, sorts) with
TPU-friendly primitives:

- **group-by = lexicographic sort + segmented reduction.**  No pointer-chasing hash map: rows
  are lexsorted on the key lanes (exact — dictionary codes make string keys integer), group
  boundaries are detected by comparing adjacent rows, and aggregates are `jax.ops.segment_*`
  reductions.  The reference's sort-based fallback for huge-NDV aggs (`SpillableAggHashMap`)
  is here the *primary* strategy because sort is what the hardware does well.
- **hash join = hash + sort + merged range lookup.**  The build rows are ordered by a 64-bit
  key hash, and every probe slot is given its range of candidates in that order by a MERGE:
  both sides' hashes are sorted as one lane, a running count of the build slots along it
  gives each probe slot its range, a second sort hands the ranges back in probe-row order,
  and nothing is gathered (`_merge_ranges`).  Every candidate pair is then verified against
  the actual key columns, so hash collisions cost duplicates-filtered work, never
  correctness.  This is the flat-array open-addressing idea of `ConcurrentRawHashTable`
  (Appendix A) re-expressed without scatter contention.  (Sorts vectorize on the chip and
  dependent gathers do not: a uint64 lane is two uint32 lanes there, a sort of 6.3M hashes
  with their ids is 0.025 s and ONE gather pass over as many probe slots 0.05-0.12 s, of
  which a search of the sorted lane makes seven, behind a prefix directory, to forty.
  PERF.md, PR 26 and PR 34.)

All kernels are fixed-shape: output capacity is a static argument and kernels report
`overflow` so the host can re-bucket and retry (the dynamic-shape escape hatch, SURVEY.md
§7.3).  Dead rows are carried via `live` masks, never compacted implicitly.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galaxysql_tpu.runtime import exec_platform

# ---------------------------------------------------------------------------
# hashing
# ---------------------------------------------------------------------------

_M1 = np.uint64(0xff51afd7ed558ccd)
_M2 = np.uint64(0xc4ceb9fe1a85ec53)
_GOLDEN = np.uint64(0x9e3779b97f4a7c15)


def _mix64(h):
    h = h ^ (h >> 33)
    h = h * _M1
    h = h ^ (h >> 33)
    h = h * _M2
    h = h ^ (h >> 33)
    return h


def hash_columns(cols: Sequence[Tuple[Any, Optional[Any]]]) -> Any:
    """Combine key columns (data, valid) into one uint64 hash lane.

    NULL contributes a distinct tag so NULL keys group together but a verify pass still
    decides join-match semantics (SQL: NULL never equals NULL in joins).
    """
    h = None
    for data, valid in cols:
        lane = _mix64(data.astype(jnp.uint64))
        if valid is not None:
            lane = jnp.where(valid, lane, jnp.uint64(0xdeadbeefcafebabe))
        h = lane if h is None else _mix64(h * np.uint64(31) + lane + _GOLDEN)
    assert h is not None
    return h


# ---------------------------------------------------------------------------
# group-by
# ---------------------------------------------------------------------------

class AggSpec(NamedTuple):
    kind: str  # 'sum' | 'count' | 'count_star' | 'min' | 'max' | 'sum_float'
    # operand index into the inputs list (-1 for count_star)
    arg: int


class GroupByResult(NamedTuple):
    keys: Tuple[Tuple[Any, Any], ...]  # per key: (data [max_groups], valid-or-None)
    aggs: Tuple[Tuple[Any, Any], ...]  # per agg: (data [max_groups], valid-or-None)
    live: Any                      # [max_groups] bool — which output slots are real groups
    num_groups: Any                # scalar int32
    overflow: Any                  # scalar bool


def sort_groupby(keys: Sequence[Tuple[Any, Optional[Any]]],
                 inputs: Sequence[Tuple[Any, Optional[Any]]],
                 specs: Sequence[AggSpec],
                 live: Any,
                 max_groups: int) -> GroupByResult:
    """Grouped aggregation.  `keys`/`inputs` are (data, valid) lanes of equal length n.

    TPU note: after the lexsort, groups are CONTIGUOUS runs, so every reduction is a
    cumulative scan + gathers at run boundaries.  No `segment_sum`/scatter anywhere —
    XLA scatters serialize on TPU and were measured 1000x slower than this formulation.
    """
    n = keys[0][0].shape[0] if keys else live.shape[0]
    dead = ~live

    # null flag participates in grouping (SQL GROUP BY: NULLs form one group)
    key_lanes: List[Any] = []
    for data, valid in keys:
        if valid is not None:
            key_lanes.append(~valid)
            key_lanes.append(jnp.where(valid, data, jnp.zeros_like(data)))
        else:
            key_lanes.append(data)

    with jax.named_scope("groupby/sort"):
        # lexsort: last key is primary => (minor..major); dead rows pushed to the end
        order = jnp.lexsort(tuple(reversed([dead.astype(jnp.int8)] + key_lanes))) \
            if key_lanes else jnp.argsort(dead.astype(jnp.int8), stable=True)
        live_s = live[order]
        sorted_lanes = [k[order] for k in key_lanes]

    with jax.named_scope("groupby/boundaries"):
        if sorted_lanes:
            prev_differs = jnp.zeros(n, dtype=jnp.bool_)
            for lane in sorted_lanes:
                prev_differs = prev_differs | jnp.concatenate(
                    [jnp.ones(1, dtype=jnp.bool_), lane[1:] != lane[:-1]])
            new_group = prev_differs & live_s
            new_group = new_group.at[0].set(live_s[0])
        else:
            new_group = jnp.zeros(n, dtype=jnp.bool_).at[0].set(live_s[0])

        num_groups = jnp.sum(new_group.astype(jnp.int32))
        overflow = num_groups > max_groups

        # run starts: positions of new_group, padded with n (a virtual end sentinel)
        (starts_raw,) = jnp.nonzero(new_group, size=max_groups + 1, fill_value=n)
        starts = starts_raw[:max_groups]                # [G] start row of group g
        ends = starts_raw[1:max_groups + 1]             # [G] start of the next group
        # dead rows sort to the end, so group g covers sorted rows [starts[g], ends[g]);
        # the LAST live group's end is the count of live rows, not n
        n_live = jnp.sum(live_s.astype(jnp.int32))
        ends = jnp.minimum(ends, n_live)
        gvalid = starts < n_live                               # real group slots
        starts_c = jnp.clip(starts, 0, max(n - 1, 0))

    def run_reduce_sum(masked):
        c = jnp.cumsum(masked, axis=0)
        c0 = jnp.concatenate([jnp.zeros(1, dtype=c.dtype), c])
        return c0[ends] - c0[starts_c]

    with jax.named_scope("groupby/reduce"):
        out_keys = []
        out_key_valid = []
        for data, valid in keys:
            out_keys.append(data[order][starts_c])
            out_key_valid.append(None if valid is None else valid[order][starts_c])

        out_aggs: List[Tuple[Any, Any]] = []
        for spec in specs:
            if spec.kind == "count_star":
                cnt = run_reduce_sum(live_s.astype(jnp.int64))
                out_aggs.append((cnt, None))
                continue
            data, valid = inputs[spec.arg]
            d_s = data[order]
            v_s = valid[order] if valid is not None else None
            present = live_s if v_s is None else (live_s & v_s)
            if spec.kind == "count":
                out_aggs.append((run_reduce_sum(present.astype(jnp.int64)), None))
            elif spec.kind in ("sum", "sum_float"):
                if jnp.issubdtype(d_s.dtype, jnp.floating):
                    masked = jnp.where(present, d_s, jnp.zeros((), dtype=d_s.dtype))
                else:
                    masked = jnp.where(present, d_s.astype(jnp.int64), 0)
                s = run_reduce_sum(masked)
                nonempty = run_reduce_sum(present.astype(jnp.int32)) > 0
                out_aggs.append((s, nonempty))
            elif spec.kind in ("min", "max"):
                if jnp.issubdtype(d_s.dtype, jnp.floating):
                    neutral = jnp.array(np.inf if spec.kind == "min" else -np.inf,
                                        d_s.dtype)
                else:
                    info = jnp.iinfo(d_s.dtype)
                    neutral = jnp.array(info.max if spec.kind == "min" else info.min,
                                        d_s.dtype)
                masked = jnp.where(present, d_s, neutral)
                # segmented running min/max restarting at each run boundary; the last
                # element of each run then holds the run's reduction
                m = _segmented_scan(masked, new_group, spec.kind == "min")
                last = jnp.clip(ends - 1, 0, max(n - 1, 0))
                nonempty = run_reduce_sum(present.astype(jnp.int32)) > 0
                out_aggs.append((m[last], nonempty))
            else:
                raise ValueError(f"unknown agg kind {spec.kind}")

    out_live = gvalid & (jnp.arange(max_groups, dtype=jnp.int32) <
                         jnp.minimum(num_groups, max_groups))
    return GroupByResult(tuple(zip(out_keys, out_key_valid)), tuple(out_aggs), out_live,
                         jnp.minimum(num_groups, max_groups).astype(jnp.int32), overflow)


def matmul_groupby(keys: Sequence[Tuple[Any, Optional[Any]]],
                   inputs: Sequence[Tuple[Any, Optional[Any]]],
                   specs: Sequence[AggSpec],
                   live: Any,
                   domains: Sequence[int]) -> GroupByResult:
    """Small-domain grouped aggregation on the MXU: one-hot int8 matmul, no sort.

    When every group key has a statically known small domain (dictionary-encoded
    strings, booleans), the group id enumerates the full key cross product and the
    aggregation becomes `A^T @ onehot(gid)` — an int8 x int8 -> int32 matmul that
    runs on the MXU systolic array instead of the O(n log n) lexsort of
    `sort_groupby` (reference seam: `HashAggExec.java:37` + `AggOpenHashMap`).

    Exact int64 sums via byte-limb decomposition: each 64-bit value contributes 8
    bias-corrected byte lanes (byte - 128 fits int8); per-group limb sums are
    recombined with shifts mod 2**64, so two's-complement wraparound reproduces
    int64 arithmetic exactly.  min/max use masked reductions over the (tiny)
    domain.  Floats are NOT supported for sum (caller falls back to sort_groupby).

    Output slots enumerate the domain in (major key .. minor key) order with NULL
    sorting last — the same group order sort_groupby produces — but live groups
    are NOT compacted to a prefix; `live` marks the non-empty slots.  `overflow`
    is always False (capacity is the static domain).
    """
    n = live.shape[0]
    gid, sizes, D = _domain_gid(keys, domains, n)

    # lane plan: [ones] + [present per distinct input] + [8 limbs per sum input]
    present_lane: dict = {}
    present_of: List[Any] = []
    for spec in specs:
        if spec.arg >= 0 and spec.arg not in present_lane:
            dta, val = inputs[spec.arg]
            present_lane[spec.arg] = len(present_of)
            present_of.append(live if val is None else (live & val))
    sum_args = sorted({s.arg for s in specs if s.kind in ("sum",) and s.arg >= 0})
    lanes: List[Any] = [live.astype(jnp.int8)]
    for a in present_of:
        lanes.append(a.astype(jnp.int8))
    limb_base: dict = {}
    for a in sum_args:
        dta, val = inputs[a]
        pres = present_of[present_lane[a]]
        v = jnp.where(pres, dta.astype(jnp.int64), jnp.int64(0))
        limb_base[a] = len(lanes)
        for j in range(8):
            byte = ((v >> jnp.int64(8 * j)) & jnp.int64(0xFF)).astype(jnp.int32)
            lanes.append((byte - 128).astype(jnp.int8))
    A = jnp.stack(lanes, axis=1)  # [n, L] int8

    # blocked contraction: int32 accumulators stay exact while n_chunk*127 < 2^31
    CHUNK = 4_000_000
    acc = jnp.zeros((A.shape[1], D), dtype=jnp.int64)
    for s0 in range(0, max(n, 1), CHUNK):
        s1 = min(s0 + CHUNK, n)
        if s1 <= s0:
            break
        oh = (gid[s0:s1, None] == jnp.arange(D, dtype=jnp.int32)[None, :])
        oh = (oh & live[s0:s1, None]).astype(jnp.int8)
        part = jax.lax.dot_general(
            A[s0:s1], oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + part.astype(jnp.int64)

    # ones/present lanes were appended as raw 0/1 int8 (no bias): no correction
    live_cnt = acc[0]
    out_live = live_cnt > 0
    num_groups = jnp.sum(out_live.astype(jnp.int32))

    def decode_sum(a: int) -> Any:
        base = limb_base[a]
        total = jnp.zeros(D, dtype=jnp.int64)
        for j in range(8):
            byte_sum = acc[base + j] + 128 * live_cnt
            total = total + (byte_sum << jnp.int64(8 * j))
        return total

    # output key lanes decode the slot index back into per-key codes
    idx = jnp.arange(D, dtype=jnp.int32)
    out_keys = _domain_out_keys(keys, domains, sizes, D)

    out_aggs: List[Tuple[Any, Any]] = []
    for spec in specs:
        if spec.kind == "count_star":
            out_aggs.append((live_cnt.astype(jnp.int64), None))
            continue
        pres = present_of[present_lane[spec.arg]]
        pres_cnt = acc[1 + present_lane[spec.arg]]
        if spec.kind == "count":
            out_aggs.append((pres_cnt.astype(jnp.int64), None))
        elif spec.kind == "sum":
            out_aggs.append((decode_sum(spec.arg), pres_cnt > 0))
        elif spec.kind in ("min", "max"):
            dta, _val = inputs[spec.arg]
            if jnp.issubdtype(dta.dtype, jnp.floating):
                neutral = jnp.array(np.inf if spec.kind == "min" else -np.inf,
                                    dta.dtype)
            else:
                info = jnp.iinfo(dta.dtype)
                neutral = jnp.array(info.max if spec.kind == "min" else info.min,
                                    dta.dtype)
            # masked reduce over the domain: [n, D] is generated, fused into the
            # reduction by XLA (never materialized at full n x D for small D)
            sel = (gid[:, None] == idx[None, :]) & pres[:, None]
            m = jnp.where(sel, dta[:, None], neutral)
            red = jnp.min(m, axis=0) if spec.kind == "min" else jnp.max(m, axis=0)
            out_aggs.append((red, pres_cnt > 0))
        else:
            raise ValueError(f"unsupported matmul agg kind {spec.kind}")

    return GroupByResult(tuple(out_keys), tuple(out_aggs), out_live,
                         num_groups.astype(jnp.int32), jnp.bool_(False))


def _domain_gid(keys, domains, n):
    """Encode small-domain key lanes into one dense group id (NULL slot last per
    key) plus per-key sizes; shared by the matmul and scatter formulations."""
    sizes: List[int] = []
    effs: List[Any] = []
    for (data, valid), dom in zip(keys, domains):
        d = jnp.clip(data.astype(jnp.int32), 0, dom - 1)
        size = dom + (1 if valid is not None else 0)
        effs.append(d if valid is None else jnp.where(valid, d, jnp.int32(dom)))
        sizes.append(size)
    D = 1
    for s in sizes:
        D *= s
    gid = jnp.zeros(n, dtype=jnp.int32)
    for eff, size in zip(effs, sizes):
        gid = gid * size + eff
    return gid, sizes, D


def _domain_out_keys(keys, domains, sizes, D):
    """Decode domain slot indices back into per-key code lanes (matmul layout)."""
    idx = jnp.arange(D, dtype=jnp.int32)
    out_keys: List[Tuple[Any, Any]] = []
    stride = D
    for (data, valid), dom, size in zip(keys, domains, sizes):
        stride //= size
        slot = (idx // stride) % size
        kd = jnp.clip(slot, 0, dom - 1).astype(data.dtype)
        kv = None if valid is None else (slot < dom)
        out_keys.append((kd, kv))
    return out_keys


def scatter_groupby(keys: Sequence[Tuple[Any, Optional[Any]]],
                    inputs: Sequence[Tuple[Any, Optional[Any]]],
                    specs: Sequence[AggSpec],
                    live: Any,
                    domains: Sequence[int]) -> GroupByResult:
    """Small-domain grouped aggregation via scatter-add: the XLA:CPU twin of
    `matmul_groupby`.

    Same contract and slot layout as `matmul_groupby` (domain cross product,
    NULL slot last, live marks non-empty slots, overflow always False), but the
    reduction is `jax.ops.segment_*` — on CPU, XLA lowers scatters to tight
    native loops (measured ~7x faster than the one-hot int8 matmul at 1.2M
    rows), while on TPU scatters serialize and the matmul path wins.  Float
    sums are supported here (no byte-limb decomposition needed: segment_sum
    accumulates in the input dtype, matching `sort_groupby`)."""
    n = live.shape[0]
    gid, sizes, D = _domain_gid(keys, domains, n)
    # dead rows land in a scratch slot D that every reduction slices off
    seg = jnp.where(live, gid, jnp.int32(D))

    live_cnt = jax.ops.segment_sum(live.astype(jnp.int64), seg,
                                   num_segments=D + 1)[:D]
    out_live = live_cnt > 0
    num_groups = jnp.sum(out_live.astype(jnp.int32))

    present_of: dict = {}
    pres_cnt: dict = {}
    for spec in specs:
        if spec.arg >= 0 and spec.arg not in present_of:
            dta, val = inputs[spec.arg]
            p = live if val is None else (live & val)
            present_of[spec.arg] = p
            pres_cnt[spec.arg] = jax.ops.segment_sum(
                p.astype(jnp.int64), seg, num_segments=D + 1)[:D]

    out_keys = _domain_out_keys(keys, domains, sizes, D)

    out_aggs: List[Tuple[Any, Any]] = []
    for spec in specs:
        if spec.kind == "count_star":
            out_aggs.append((live_cnt, None))
            continue
        dta, _val = inputs[spec.arg]
        pres = present_of[spec.arg]
        if spec.kind == "count":
            out_aggs.append((pres_cnt[spec.arg], None))
        elif spec.kind in ("sum", "sum_float"):
            if jnp.issubdtype(dta.dtype, jnp.floating):
                masked = jnp.where(pres, dta, jnp.zeros((), dtype=dta.dtype))
            else:
                masked = jnp.where(pres, dta.astype(jnp.int64), jnp.int64(0))
            s = jax.ops.segment_sum(masked, seg, num_segments=D + 1)[:D]
            out_aggs.append((s, pres_cnt[spec.arg] > 0))
        elif spec.kind in ("min", "max"):
            if jnp.issubdtype(dta.dtype, jnp.floating):
                neutral = jnp.array(np.inf if spec.kind == "min" else -np.inf,
                                    dta.dtype)
            else:
                info = jnp.iinfo(dta.dtype)
                neutral = jnp.array(info.max if spec.kind == "min" else info.min,
                                    dta.dtype)
            masked = jnp.where(pres, dta, neutral)
            red_fn = jax.ops.segment_min if spec.kind == "min" else jax.ops.segment_max
            red = red_fn(masked, seg, num_segments=D + 1)[:D]
            # empty slots come back as the op's own identity; normalize to neutral
            red = jnp.where(pres_cnt[spec.arg] > 0, red, neutral.astype(dta.dtype)) \
                if jnp.issubdtype(dta.dtype, jnp.floating) else red
            out_aggs.append((red, pres_cnt[spec.arg] > 0))
        else:
            raise ValueError(f"unsupported scatter agg kind {spec.kind}")

    return GroupByResult(tuple(out_keys), tuple(out_aggs), out_live,
                         num_groups.astype(jnp.int32), jnp.bool_(False))


def _ident_lanes(keys):
    """Per-key (data_canon, valid) identity lanes for hashing/equality.

    Floats are canonicalized (-0.0 -> +0.0, NaN -> one bit pattern) then
    bitcast to same-width ints so hash and equality agree with SQL GROUP BY
    semantics (0.0 == -0.0 one group, all NaNs one group, NULLs one group)."""
    out = []
    for data, valid in keys:
        if jnp.issubdtype(data.dtype, jnp.floating):
            d = jnp.where(data == 0, jnp.zeros((), data.dtype), data)
            d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, data.dtype), d)
            width = jnp.int32 if data.dtype == jnp.float32 else jnp.int64
            d = jax.lax.bitcast_convert_type(d, width)
        else:
            d = data
        if valid is not None:
            d = jnp.where(valid, d, jnp.zeros((), d.dtype))
        out.append((d, valid))
    return out


def _hash_place(ident: Sequence[Tuple[Any, Optional[Any]]], live: Any,
                s0: Any, step: Any, M: int, max_rounds: int):
    """Slot placement for `hash_groupby`: vectorized scatter-min election
    rounds with an early-exit while_loop."""
    n = live.shape[0]
    rowid = jnp.arange(n, dtype=jnp.int32)
    sentinel = jnp.int32(n)

    def cond(state):
        r, rep, resolved, gid = state
        return (r < max_rounds) & jnp.any(~resolved)

    def body(state):
        r, rep, resolved, gid = state
        s = ((s0 + r.astype(jnp.uint64) * step) &
             jnp.uint64(M - 1)).astype(jnp.int32)
        occupied = rep[s] != sentinel
        cand = jnp.where(resolved | occupied, sentinel, rowid)
        rep = rep.at[s].min(cand)
        owner = rep[s]
        safe = jnp.clip(owner, 0, max(n - 1, 0))
        same = owner != sentinel
        for d, valid in ident:
            same = same & (d[safe] == d)
            if valid is not None:
                same = same & (valid[safe] == valid)
        newly = ~resolved & same
        gid = jnp.where(newly, s, gid)
        return r + jnp.uint64(1), rep, resolved | newly, gid

    state = (jnp.uint64(0), jnp.full(M, sentinel, jnp.int32),
             ~live, jnp.zeros(n, jnp.int32))
    _, rep, resolved, gid = jax.lax.while_loop(cond, body, state)
    return rep, resolved, gid


def hash_groupby(keys: Sequence[Tuple[Any, Optional[Any]]],
                 inputs: Sequence[Tuple[Any, Optional[Any]]],
                 specs: Sequence[AggSpec],
                 live: Any,
                 max_groups: int,
                 max_rounds: int = 64) -> GroupByResult:
    """General grouped aggregation via open-addressing hash slots — no sort.

    The XLA:CPU twin of `sort_groupby`: on CPU, XLA's comparator sorts are
    single-threaded and catastrophically slow (lexsort of 1.2M rows ~1.3s)
    while scatters are fast (~10ms), so group ids are assigned by hashing keys
    into a power-of-two slot table.  Each round, unresolved rows probing an
    EMPTY slot elect an owner by scatter-min on row index; every row then
    verifies its actual key lanes against the owner's (hash collisions cost
    extra rounds, never correctness).  Rows whose keys match the owner adopt
    the slot as their group id; the rest re-probe with an odd per-key stride.
    Aggregation is then `jax.ops.segment_*` by slot.

    Output slots are in hash order, NOT compacted — `live` marks real groups,
    the same contract `matmul_groupby` established.  `overflow` is True when
    placement fails within `max_rounds` (distinct groups exceed capacity or
    pathological clustering); callers retry with doubled `max_groups`."""
    n = live.shape[0] if not keys else keys[0][0].shape[0]
    cap = max(16, min(max_groups, n))
    M = 1 << int(cap * 2 - 1).bit_length()  # load factor <= 0.5 at capacity

    ident = _ident_lanes(keys)
    h = hash_columns(ident)
    s0 = h & jnp.uint64(M - 1)
    # odd stride => full cycle mod the power-of-two table size
    step = ((h >> jnp.uint64(32)) << jnp.uint64(1)) | jnp.uint64(1)

    sentinel = jnp.int32(n)
    rep, resolved, gid = _hash_place(ident, live, s0, step, M, max_rounds)
    overflow = jnp.any(~resolved)

    placed = resolved & live
    seg = jnp.where(placed, gid, jnp.int32(M))

    live_cnt = jax.ops.segment_sum(live.astype(jnp.int64), seg,
                                   num_segments=M + 1)[:M]
    out_live = rep != sentinel
    num_groups = jnp.sum(out_live.astype(jnp.int32))

    safe_rep = jnp.clip(rep, 0, max(n - 1, 0))
    out_keys = []
    for data, valid in keys:
        out_keys.append((data[safe_rep],
                         None if valid is None else (valid[safe_rep] & out_live)))

    present_of: dict = {}
    pres_cnt: dict = {}
    for spec in specs:
        if spec.arg >= 0 and spec.arg not in present_of:
            dta, val = inputs[spec.arg]
            p = placed if val is None else (placed & val)
            present_of[spec.arg] = p
            pres_cnt[spec.arg] = jax.ops.segment_sum(
                p.astype(jnp.int64), seg, num_segments=M + 1)[:M]

    out_aggs: List[Tuple[Any, Any]] = []
    for spec in specs:
        if spec.kind == "count_star":
            out_aggs.append((live_cnt, None))
            continue
        dta, _val = inputs[spec.arg]
        pres = present_of[spec.arg]
        if spec.kind == "count":
            out_aggs.append((pres_cnt[spec.arg], None))
        elif spec.kind in ("sum", "sum_float"):
            if jnp.issubdtype(dta.dtype, jnp.floating):
                masked = jnp.where(pres, dta, jnp.zeros((), dtype=dta.dtype))
            else:
                masked = jnp.where(pres, dta.astype(jnp.int64), jnp.int64(0))
            s = jax.ops.segment_sum(masked, seg, num_segments=M + 1)[:M]
            out_aggs.append((s, pres_cnt[spec.arg] > 0))
        elif spec.kind in ("min", "max"):
            if jnp.issubdtype(dta.dtype, jnp.floating):
                neutral = jnp.array(np.inf if spec.kind == "min" else -np.inf,
                                    dta.dtype)
            else:
                info = jnp.iinfo(dta.dtype)
                neutral = jnp.array(info.max if spec.kind == "min" else info.min,
                                    dta.dtype)
            masked = jnp.where(pres, dta, neutral)
            red_fn = jax.ops.segment_min if spec.kind == "min" else jax.ops.segment_max
            red = red_fn(masked, seg, num_segments=M + 1)[:M]
            red = jnp.where(pres_cnt[spec.arg] > 0, red, neutral.astype(dta.dtype)) \
                if jnp.issubdtype(dta.dtype, jnp.floating) else red
            out_aggs.append((red, pres_cnt[spec.arg] > 0))
        else:
            raise ValueError(f"unsupported hash agg kind {spec.kind}")

    return GroupByResult(tuple(out_keys), tuple(out_aggs), out_live,
                         num_groups.astype(jnp.int32), overflow)


def prefer_scatter() -> bool:
    """Kernel-formulation choice is a backend property: XLA:CPU lowers scatters
    to fast native loops but its comparator sorts are single-threaded (measured
    1.3s to lexsort 1.2M rows vs ~10ms for a segment_sum); TPU is the inverse
    (bitonic sorts + MXU matmuls are fast; gathers are its slow thing, 8-17 ns
    a 32-bit word on a v5e and a 64-bit lane is two words, which is why the
    TPU join counts its gathered words: PERF.md, PR 26 and PR 30; scatters
    that combine into shared slots serialize, one of distinct 32-bit updates
    measured about 5 ns an update).  Asks where
    the program being traced will RUN, not what the default backend is: a
    program under the TP path's CPU pin gets the CPU formulation."""
    return exec_platform() == "cpu"


def groupby(keys, inputs, specs, live, max_groups, domains=None):
    """Backend-adaptive grouped aggregation dispatch (see `prefer_scatter`).

    `domains` (per-key small static domains, or None) selects the dense-slot
    formulations; float SUM is only a restriction for the matmul byte-limb
    path, not for scatter."""
    if domains is None and not keys:
        domains = []  # global aggregation: one dense slot, never hash/sort
    if domains is not None:
        if prefer_scatter():
            return scatter_groupby(keys, inputs, specs, live, domains)
        float_sum = any(
            s.kind in ("sum", "sum_float") and s.arg >= 0 and
            jnp.issubdtype(inputs[s.arg][0].dtype, jnp.floating) for s in specs)
        if not float_sum:
            with jax.named_scope("groupby/matmul"):
                return matmul_groupby(keys, inputs, specs, live, domains)
    if prefer_scatter():
        return hash_groupby(keys, inputs, specs, live, max_groups)
    return sort_groupby(keys, inputs, specs, live, max_groups)


def _segmented_scan(x, reset, is_min: bool):
    """Running min/max that restarts where `reset` is True (log-depth, no scatter).

    min and max are separate combiners on purpose: computing max as -scan_min(-x)
    would wrap the integer neutral (-INT_MIN == INT_MIN) and poison groups that
    contain NULLs."""
    pick = jnp.minimum if is_min else jnp.maximum

    def combine(a, b):
        av, ar = a
        bv, br = b
        v = jnp.where(br, bv, pick(av, bv))
        return v, ar | br

    vals, _ = jax.lax.associative_scan(combine, (x, reset))
    return vals


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

class JoinPairs(NamedTuple):
    build_idx: Any      # [cap] int32 indices into build arrays
    probe_idx: Any      # [cap] int32 indices into probe arrays
    live: Any           # [cap] bool — verified pairs
    probe_matched: Any  # [n_probe] bool — probe rows with >=1 verified match
    probe_starts: Any   # [n_probe] int64 — first pair slot of each probe row
    probe_offsets: Any  # [n_probe] int64 — end pair slot of each probe row
    overflow: Any       # scalar bool
    # scalar int32 — passes the expansion's running maximum took (sorted
    # formulation only; `bit_length(most pairs of a probe row - 1)`)
    expand_levels: Any = None


def _effective_live(keys, live):
    m = live
    for _, valid in keys:
        if valid is not None:
            m = m & valid
    return m


def hash_join_pairs(build_keys: Sequence[Tuple[Any, Optional[Any]]],
                    probe_keys: Sequence[Tuple[Any, Optional[Any]]],
                    build_live: Any,
                    probe_live: Any,
                    cap: int) -> JoinPairs:
    """Equi-join match enumeration: returns verified (build, probe) index pairs.

    NULL join keys never match (SQL semantics): rows with any NULL key are masked out of
    both sides before hashing.  Backend-adaptive: the TPU formulation orders the
    build rows by their hash (one `argsort` of `nb` slots), finds each probe
    slot's range of candidates by sorting both sides' hashes as one lane
    (`_merge_ranges`: two more sorts, of `nb + npr` slots, and streaming
    passes between them, no gather), then
    expands the ranges into pair slots with one scatter of row ids and a
    running maximum (`_expand_rows`; two gathered words a pair slot where a
    whole-lane search of the 64-bit running count makes about 50); the CPU
    formulation buckets the build side into a slot-table CSR
    and probes by direct gather (XLA:CPU searchsorted costs ~200ms per 1.2M
    probes — 18 full gather passes — while scatters are native loops)."""
    if prefer_scatter():
        return _hash_join_pairs_table(build_keys, probe_keys, build_live,
                                      probe_live, cap)
    return _hash_join_pairs_sorted(build_keys, probe_keys, build_live,
                                   probe_live, cap)


# hash given to dead build rows: sorts past every live one and matches no probe,
# because live hashes are held to the value below it (two live hashes made equal
# there are one more collision for `verify`)
_DEAD_HASH = np.uint64(0xffffffffffffffff)
_TOP_LIVE_HASH = np.uint64(0xfffffffffffffffe)


def full_search_depth(nb: int) -> int:
    """Levels a binary search over a whole `nb`-slot lane runs (what
    `jnp.searchsorted` pays for every query): the depth `expand_levels` of
    `JoinPairs` is read against."""
    return int(nb).bit_length()


def _merge_ranges(h_b, h_p):
    """For every probe hash its range of equal hashes among the live build
    hashes in sorted order, `(left, run)`: `left` as `searchsorted(sorted live
    build hashes, h_p, "left")` gives it, `run` the build slots that hold
    `h_p`, both over the hashes' upper 63 bits, with no search and no gather.

    The build hashes and the probe hashes are sorted as ONE lane whose lowest
    bit says which side a slot is of, so at equal 63 bits build slots come
    before probe slots; the live build slots counted up to a probe slot are
    then `left + run`, and those counted before its run of equal hashes are
    `left` (dead rows keep `_DEAD_HASH`, sort behind every live one and are
    not counted).  Two hashes that differ in the 64th bit alone share a range:
    one more collision for `verify`, as two keys with one hash are.  The count
    at a run's first slot is carried over the run by a running maximum (the
    counts never fall): doubling strides, shifts, until every PROBE slot has
    seen its run's start, `bit_length(longest run - 1)` passes of a 32-bit
    lane (the loop of `_expand_rows`; `lax.cummax` is a reduce-window that
    costs the chip's compiler half a minute, `associative_scan` 717 s).  A
    second sort, by slot id, hands both lanes back in probe-row order.

    It pays by the slot of BOTH sides, whichever is the larger.  Prices on a
    v5e (PERF.md, PR 34), at 1,572,864 + 6,291,456 slots: this sort 22 ms (33
    with the id as a second key in place of the tag bit), the sort back 21
    (two scatters to the probe rows, random targets, 101: 6.4 ns an update),
    the count 2.  A bounded search behind a prefix directory, some fifteen
    gathered 32-bit words a probe slot at 6.5-17 ns, took 1,136 ms where this
    takes 50 with the build side's `argsort`, and lost at every ratio of the
    two sides a cell has (202 against 61 at 6,291,456 x 65,536)."""
    nb, npr = h_b.shape[0], h_p.shape[0]
    n = nb + npr
    probe_side = jnp.uint64(1)
    lane = jnp.concatenate([jnp.where(h_b == _DEAD_HASH, h_b, h_b & ~probe_side),
                            h_p | probe_side])
    lane, ids = jax.lax.sort((lane, jnp.arange(n, dtype=jnp.int32)),
                             num_keys=1, is_stable=False)
    # a dead build row's lowest bit is set like a probe slot's: its id tells
    counted = (ids < nb) & ((lane & probe_side) == 0)
    upto = jnp.cumsum(counted.astype(jnp.int32))  # live build slots up to and at
    first_of_run = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), (lane[1:] | probe_side) != (lane[:-1] | probe_side)])
    nothing = jnp.full(n, -1, jnp.int32)
    before = jnp.where(first_of_run, upto - counted, nothing)

    def look_back(state):
        stride, before = state
        behind = jax.lax.dynamic_slice(jnp.concatenate([nothing, before]),
                                       (n - stride,), (n,))
        return stride * 2, jnp.maximum(before, behind)

    before = jax.lax.while_loop(
        lambda state: jnp.any((state[1] < 0) & (ids >= nb)), look_back,
        (jnp.int32(1), before))[1]
    _, left, end = jax.lax.sort((ids, before, upto), num_keys=1, is_stable=False)
    left = left[nb:]
    return left, end[nb:] - left


def _expand_rows(starts, offsets, cap: int):
    """The probe row that owns each of `cap` pair slots, as
    `searchsorted(offsets, arange(cap), "right")` clipped to a row gives it,
    which slots hold a pair, and the passes it took:
    `(p_of, pair_live, passes)`.

    Each probe row with a pair writes its id at its first pair slot, in one
    32-bit scatter (rows without one aim at `cap` and are dropped, so the
    slots written are distinct; about 5 ns an update on a v5e, where that
    search gathers two words a level at 8-17 ns each), and a running
    maximum carries the ids forward over the rows' other slots: by
    doubling strides, shifts and no gathers, until every slot with a pair
    has seen its row, which is `bit_length(most pairs of a row - 1)` passes,
    a device scalar (`lax.cummax` is the reduce-window that costs the chip's
    compiler half a minute).  None for a key-to-foreign-key join.  Slots that hold no pair read `npr - 1`."""
    npr = offsets.shape[0]
    lane = jnp.int32 if cap <= np.iinfo(np.int32).max else jnp.int64
    first_slot = jnp.where(offsets > starts, jnp.minimum(starts, cap), cap)
    nothing = jnp.full(cap, -1, jnp.int32)
    marks = nothing.at[first_slot.astype(lane)].set(
        jnp.arange(npr, dtype=jnp.int32), mode="drop")
    pair_live = jnp.arange(cap, dtype=lane) < jnp.minimum(offsets[-1], cap).astype(lane)
    marks = jnp.where(pair_live, marks, npr - 1)

    def look_back(state):
        stride, passes, rows = state
        behind = jax.lax.dynamic_slice(jnp.concatenate([nothing, rows]),
                                       (cap - stride,), (cap,))
        return stride * 2, passes + 1, jnp.maximum(rows, behind)

    _, passes, p_of = jax.lax.while_loop(
        lambda state: jnp.any(state[2] < 0), look_back,
        (jnp.int32(1), jnp.int32(0), marks))
    return p_of, pair_live, passes


def _candidate_ranges(build_keys, probe_keys, b_live):
    """`(perm, left, run)`: the build rows ordered by their 64-bit key hash
    (dead rows behind every live one) and, for every probe slot, the range
    `perm[left : left + run]` of build rows that hold its hash
    (`_merge_ranges`; inside a range the order of equal hashes is `argsort`'s
    and does not matter)."""
    with jax.named_scope("join_pairs/sort"):
        h_b = jnp.minimum(hash_columns(build_keys), _TOP_LIVE_HASH)
        # dead build rows get a sentinel hash sorted to the end and never matched
        h_b = jnp.where(b_live, h_b, _DEAD_HASH)
        perm = jnp.argsort(h_b)
    with jax.named_scope("join_pairs/probe"):
        h_p = jnp.minimum(hash_columns(probe_keys), _TOP_LIVE_HASH)
        return (perm, *_merge_ranges(h_b, h_p))


def _hash_join_pairs_sorted(build_keys, probe_keys, build_live, probe_live,
                            cap: int) -> JoinPairs:
    """TPU join: order the build rows by hash, find every probe hash's range of
    candidates (`_candidate_ranges`), expand the ranges into `cap` pair slots
    (`_expand_rows` says which probe row owns a slot; the slot's build position
    is one gathered word more), verify the pairs on the key lanes."""
    b_live = _effective_live(build_keys, build_live)
    p_live = _effective_live(probe_keys, probe_live)
    nb = build_keys[0][0].shape[0]
    npr = probe_keys[0][0].shape[0]
    if nb == 0 or npr == 0:  # nothing to gather from: no candidate, no pair
        none = jnp.zeros(cap, jnp.int32)
        ends = jnp.zeros(npr, jnp.int64)
        return JoinPairs(none, none, jnp.zeros(cap, jnp.bool_),
                         jnp.zeros(npr, jnp.bool_), ends, ends,
                         jnp.bool_(False), jnp.int32(0))

    perm, left, run = _candidate_ranges(build_keys, probe_keys, b_live)

    with jax.named_scope("join_pairs/probe"):
        counts = jnp.where(p_live, run.astype(jnp.int64), 0)

        offsets = jnp.cumsum(counts)
        total = offsets[-1]
        overflow = total > cap
        starts = offsets - counts

    with jax.named_scope("join_pairs/expand"):
        # ragged expansion: slot j -> probe row p, k-th candidate.  `left` less
        # the row's first slot is one 32-bit lane, so slot j's build position
        # is one gathered word plus j (wrapping as `left + int32(j - start)`)
        p_of, pair_live, expand_levels = _expand_rows(starts, offsets, cap)
        first_candidate = left - starts.astype(jnp.int32)
        bpos = jnp.clip(first_candidate[p_of] + jnp.arange(cap).astype(jnp.int32),
                        0, nb - 1)
        b_of = perm.astype(jnp.int32)[bpos]  # `argsort` counts in 64 bits

    with jax.named_scope("join_pairs/verify"):
        # verify candidate pairs on the actual key lanes (hash collisions filtered here)
        verified = pair_live
        for (bd, bv), (pd, pv) in zip(build_keys, probe_keys):
            eq = bd[b_of] == pd[p_of]
            verified = verified & eq
        verified = verified & b_live[b_of] & p_live[p_of]

        # pair slots are ordered by probe row, so per-probe-row "any verified" is a
        # prefix-sum range query (a scatter that ORs into shared slots would
        # serialize; one of distinct updates costs about 5 ns each, PERF.md PR 30)
        probe_matched = probe_matched_from(verified, starts, offsets)

    return JoinPairs(b_of, p_of, verified, probe_matched, starts, offsets,
                     overflow, expand_levels)


def _any_candidate_equal(build_keys, probe_keys, b_live, order, first, count):
    """For every probe row, whether one of its `count` candidates (the build
    rows `order[first + k]`, `k < count`) has its keys: a loop over `k` that
    stops once every probe row with candidates left has found one, so a
    key-to-key match costs one pass whatever the fan-out (the first candidate
    of a run of equal 64-bit hashes is the key itself unless two keys collide)
    and only a collision, or a slot two keys share, runs a second."""
    nb = order.shape[0]
    npr = first.shape[0]
    first = first.astype(jnp.int32)
    count = count.astype(jnp.int32)

    def undecided(k, matched):
        return (k < count) & ~matched

    def look(state):
        k, matched = state
        b = order[jnp.clip(first + k, 0, nb - 1)]
        eq = b_live[b]
        for (bd, _), (pd, _) in zip(build_keys, probe_keys):
            eq = eq & (bd[b] == pd)
        return k + 1, matched | (undecided(k, matched) & eq)

    return jax.lax.while_loop(lambda state: jnp.any(undecided(*state)), look,
                              (jnp.int32(0), jnp.zeros(npr, jnp.bool_)))[1]


def _front_rows(live, slots: int):
    """The ids of the live rows, in order, in `slots` slots (the caller has
    counted: they fit), and which of those slots hold one: `(ids, held)`.  One
    scatter of row ids at their rank, about 5 ns an update on a v5e."""
    n = live.shape[0]
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    ids = jnp.zeros(slots, jnp.int32).at[jnp.where(live, rank, slots)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return ids, jnp.arange(slots, dtype=jnp.int32) <= rank[-1]


def hash_join_matched(build_keys: Sequence[Tuple[Any, Optional[Any]]],
                      probe_keys: Sequence[Tuple[Any, Optional[Any]]],
                      build_live: Any, probe_live: Any,
                      probe_slots: Optional[int] = None) -> Any:
    """What a semi or anti join without a residual needs of `hash_join_pairs`:
    `probe_matched`, exact, with no pair slot, no capacity and so no ladder,
    whatever the fan-out.  Same sort and range lookup (`_candidate_ranges`) as
    the pair enumeration; the candidates are compared in place
    (`_any_candidate_equal`).  This is the sorted (TPU) formulation; the slot
    table's is `hash_join_matched_csr`, over the CSR its caller holds.

    The lookup and the comparison pay by the probe SLOT, live or dead (the
    comparison gathers both sides' keys for every one), so a caller that has
    counted the live probe rows and found them few gives `probe_slots`, a
    bucket that holds them: the live rows' keys are moved to the front of
    that many slots (`_front_rows`), looked up there, and the answers
    scattered back."""
    b_live = _effective_live(build_keys, build_live)
    p_live = _effective_live(probe_keys, probe_live)
    nb = build_keys[0][0].shape[0]
    npr = probe_keys[0][0].shape[0]
    if nb == 0 or npr == 0:
        return jnp.zeros(npr, jnp.bool_)
    if probe_slots is not None and probe_slots < npr:
        with jax.named_scope("join_pairs/front"):
            ids, held = _front_rows(p_live, probe_slots)
            front = [(d[ids], None) for d, _ in probe_keys]  # NULLs are not live
        matched = hash_join_matched(build_keys, front, build_live, held)
        with jax.named_scope("join_pairs/front"):
            return jnp.zeros(npr, jnp.bool_).at[
                jnp.where(held, ids, npr)].set(matched, mode="drop")
    perm, left, run = _candidate_ranges(build_keys, probe_keys, b_live)
    with jax.named_scope("join_pairs/verify"):
        return _any_candidate_equal(build_keys, probe_keys, b_live,
                                    perm.astype(jnp.int32), left,
                                    jnp.where(p_live, run, 0))


def hash_join_matched_csr(build_keys, probe_keys, build_live, probe_live,
                          perm, slot_starts, slot_counts, M: int) -> Any:
    """`hash_join_matched` against a slot-table CSR (host-built or
    `_device_csr`'s): a probe row's candidates are its slot's build rows."""
    b_live = _effective_live(build_keys, build_live)
    p_live = _effective_live(probe_keys, probe_live)
    npr = probe_keys[0][0].shape[0]
    if build_keys[0][0].shape[0] == 0 or npr == 0:
        return jnp.zeros(npr, jnp.bool_)
    s_p = (hash_columns(probe_keys) & jnp.uint64(M - 1)).astype(jnp.int32)
    return _any_candidate_equal(build_keys, probe_keys, b_live, perm,
                                slot_starts[s_p],
                                jnp.where(p_live, slot_counts[s_p], 0))


def _device_csr(build_keys, build_live, nb: int):
    """Device-side CSR over the build slots: (perm, slot_starts, slot_counts,
    M).  One argsort of the SMALL side groups build row ids contiguously per
    slot; M = 4x build capacity => expected <=0.25 collision candidates per
    probe, filtered by key verification like the sorted path."""
    M = 1 << max(4, int(nb * 4 - 1).bit_length())
    # slot-id lane shared with the host-CSR path (hash + dead-row masking):
    # one definition every join formulation and the hybrid union probe reuse
    s_b = hash_join_build_slots(build_keys, build_live, M)
    perm = jnp.argsort(s_b).astype(jnp.int32)
    slot_counts = jax.ops.segment_sum(jnp.ones(nb, jnp.int32), s_b,
                                      num_segments=M + 1)[:M]
    slot_ends = jnp.cumsum(slot_counts)
    slot_starts = slot_ends - slot_counts
    return perm, slot_starts, slot_counts, M


def _expand_offsets(counts, starts, npr: int, cap: int):
    """Ragged probe->pair expansion: scatter each non-empty probe row's id at
    its first pair slot, then forward-fill with cummax (starts are unique
    among non-empty rows) — ~10x faster than searchsorted(offsets,
    arange(cap)) on XLA:CPU."""
    scatter_at = jnp.where(counts > 0, starts, jnp.int64(cap))
    p_of = jnp.zeros(cap, jnp.int32).at[scatter_at].max(
        jnp.arange(npr, dtype=jnp.int32), mode="drop")
    return jax.lax.cummax(p_of)


def _hash_join_pairs_table(build_keys, probe_keys, build_live, probe_live,
                           cap: int) -> JoinPairs:
    """CPU join: slot-table CSR over the build side, gather-probe, scatter
    expand.  Thin composition of `_device_csr` + `hash_join_probe_csr` — the
    hybrid probe rides the exact same pipeline."""
    nb = build_keys[0][0].shape[0]
    perm, slot_starts, slot_counts, M = _device_csr(build_keys, build_live, nb)
    return hash_join_probe_csr(build_keys, probe_keys, build_live, probe_live,
                               perm, slot_starts, slot_counts, M, cap)


def hash_join_build_slots(build_keys: Sequence[Tuple[Any, Optional[Any]]],
                          build_live: Any, M: int) -> Any:
    """Build-side slot ids for the host-CSR join (CPU backend).

    XLA:CPU's comparator sort is ~12x slower than numpy's introsort (measured
    106ms vs 8ms argsorting 327k int32), so the CSR construction (argsort +
    bincount of these slot ids) runs on the host; this device kernel only
    computes the slot id lane (hash + mask) that both sides must agree on.
    Dead/NULL-key rows get the scratch slot M."""
    b_live = _effective_live(build_keys, build_live)
    h_b = hash_columns(build_keys)
    s_b = (h_b & jnp.uint64(M - 1)).astype(jnp.int32)
    return jnp.where(b_live, s_b, jnp.int32(M))


def hash_join_probe_csr(build_keys, probe_keys, build_live, probe_live,
                        perm, slot_starts, slot_counts,
                        M: int, cap: int) -> JoinPairs:
    """Probe half of the CPU slot-table join against a host-built CSR.

    Identical pair enumeration to `_hash_join_pairs_table` from the probe hash
    onward; the build-side argsort/cumsum live outside (host numpy, see
    `hash_join_build_slots`).  The CSR is reused across probe batches and
    overflow retries — the build side is never re-sorted."""
    b_live = _effective_live(build_keys, build_live)
    p_live = _effective_live(probe_keys, probe_live)
    nb = build_keys[0][0].shape[0]
    npr = probe_keys[0][0].shape[0]

    h_p = hash_columns(probe_keys)
    s_p = (h_p & jnp.uint64(M - 1)).astype(jnp.int32)
    counts = jnp.where(p_live, slot_counts[s_p].astype(jnp.int64), 0)

    offsets = jnp.cumsum(counts)
    total = offsets[-1] if npr else jnp.int64(0)
    overflow = total > cap
    starts = offsets - counts

    slots = jnp.arange(cap, dtype=jnp.int64)
    p_of = _expand_offsets(counts, starts, npr, cap)
    k = slots - starts[p_of]
    pair_live = slots < jnp.minimum(total, cap)
    bpos = jnp.clip(slot_starts[s_p[p_of]].astype(jnp.int64) + k, 0,
                    max(nb - 1, 0))
    b_of = perm[bpos]

    verified = pair_live
    for (bd, bv), (pd, pv) in zip(build_keys, probe_keys):
        verified = verified & (bd[b_of] == pd[p_of])
    verified = verified & b_live[b_of] & p_live[p_of]

    probe_matched = probe_matched_from(verified, starts, offsets) \
        if npr else jnp.zeros(0, jnp.bool_)

    return JoinPairs(b_of, p_of, verified, probe_matched, starts, offsets, overflow)


def hot_key_mask(keys: Sequence[Tuple[Any, Optional[Any]]],
                 hot_hashes: Any, hot_valid: Any) -> Any:
    """Heavy-hitter classification lane for the skew-aware hybrid join.

    True where the row's combined key hash (the SAME `hash_columns` lane the
    repartition destinations derive from) is one of the `hot_hashes` runtime
    values (`hot_valid` masks the static padding slots — the hot-set size is
    a runtime property and must not retrace).  Classification is purely
    hash-based ON PURPOSE: a cold key colliding with a hot hash is classified
    hot on BOTH sides of the join, so the broadcast/shuffle lanes stay
    consistent and correctness never depends on the hot set's contents."""
    h = hash_columns(keys)
    hit = (h[:, None] == hot_hashes[None, :]) & hot_valid[None, :]
    return jnp.any(hit, axis=1)


def hash_join_probe_hybrid(build_keys: Sequence[Tuple[Any, Optional[Any]]],
                           probe_keys: Sequence[Tuple[Any, Optional[Any]]],
                           build_live: Any, probe_live: Any,
                           cap: int) -> JoinPairs:
    """Union-lane probe of the skew-aware hybrid join.

    The caller concatenates each shard's two build partitions — the broadcast
    hot lane and the hash-shuffled cold lane — and likewise the two probe
    partitions (locally-kept hot rows + shuffled cold rows); this entry
    enumerates verified pairs over the union in ONE pass with the standard
    fixed-shape/overflow contract.  Both lanes go through the same build-slot
    construction (`hash_join_build_slots` inside `_device_csr`), and the
    probe rides `hash_join_probe_csr` on EVERY backend — one implementation
    shared with the batch-streamed CSR probe instead of a re-derived pair
    enumeration per entry point."""
    nb = build_keys[0][0].shape[0]
    perm, slot_starts, slot_counts, M = _device_csr(build_keys, build_live, nb)
    return hash_join_probe_csr(build_keys, probe_keys, build_live, probe_live,
                               perm, slot_starts, slot_counts, M, cap)


def probe_matched_from(pair_live: Any, starts: Any, offsets: Any) -> Any:
    """matched[p] = any pair in [starts[p], offsets[p]) is live (prefix-sum ranges)."""
    cap = pair_live.shape[0]
    c = jnp.concatenate([jnp.zeros(1, jnp.int64),
                         jnp.cumsum(pair_live.astype(jnp.int64))])
    s = jnp.clip(starts, 0, cap)
    e = jnp.clip(offsets, 0, cap)
    return (c[e] - c[s]) > 0


def bloom_query_device(keys: Any, words: Any) -> Any:
    """Device-side bloom membership test; bit layout matches the native builder
    (galaxystore gx_bloom_build) and this module's _mix64."""
    h = _mix64(keys.astype(jnp.uint64))
    nwords = words.shape[0]
    m = jnp.uint64(nwords - 1)
    w1 = words[((h >> jnp.uint64(6)) & m).astype(jnp.int32)]
    w2 = words[((h >> jnp.uint64(38)) & m).astype(jnp.int32)]
    hit1 = (w1 >> (h & jnp.uint64(63))) & jnp.uint64(1)
    hit2 = (w2 >> ((h >> jnp.uint64(32)) & jnp.uint64(63))) & jnp.uint64(1)
    return (hit1 & hit2).astype(jnp.bool_)


# ---------------------------------------------------------------------------
# sort / topn
# ---------------------------------------------------------------------------

def sort_indices(keys: Sequence[Tuple[Any, Optional[Any], bool, bool]],
                 live: Any) -> Any:
    """Stable multi-key sort.  Each key: (data, valid, descending, nulls_first).

    Returns a permutation with live rows first in the requested order.
    MySQL default: NULLs sort first ascending, last descending.
    """
    lanes: List[Any] = []
    for data, valid, desc, nulls_first in keys:
        if jnp.issubdtype(data.dtype, jnp.floating):
            lane = -data if desc else data
        elif data.dtype == jnp.bool_:
            lane = (~data if desc else data).astype(jnp.int8)
        else:
            lane = -data.astype(jnp.int64) if desc else data.astype(jnp.int64)
        if valid is not None:
            non_null_rank = jnp.asarray(1 if nulls_first else 0, dtype=jnp.int8)
            null_rank = jnp.asarray(0 if nulls_first else 1, dtype=jnp.int8)
            lanes.append(jnp.where(valid, non_null_rank, null_rank))
            zero = jnp.zeros((), dtype=lane.dtype)
            lane = jnp.where(valid, lane, zero)
        lanes.append(lane)
    with jax.named_scope("sort/lexsort"):
        dead = (~live).astype(jnp.int8)
        order = jnp.lexsort(tuple(reversed([dead] + lanes)))
    return order


# ---------------------------------------------------------------------------
# compaction / misc
# ---------------------------------------------------------------------------

def limit_mask(live: Any, offset: int, count: int) -> Any:
    """LIMIT offset, count over live rows (order = physical order)."""
    rank = jnp.cumsum(live.astype(jnp.int64)) - 1
    return live & (rank >= offset) & (rank < offset + count)


# ---------------------------------------------------------------------------
# window functions
# ---------------------------------------------------------------------------

class WindowSpec(NamedTuple):
    kind: str    # row_number | rank | dense_rank | sum | count | min | max |
                 # lag | lead | first_value | last_value
    arg: int     # input lane index (-1 for rank-family)
    offset: int  # lag/lead distance
    # frame: 'running' (ROWS ..CURRENT), 'range' (RANGE ..CURRENT: ties share the
    # run-end value), 'whole' (entire partition)
    frame: str


def window_eval(part_keys: Sequence[Tuple[Any, Optional[Any]]],
                order_keys: Sequence[Tuple[Any, Optional[Any], bool, bool]],
                inputs: Sequence[Tuple[Any, Optional[Any]]],
                specs: Sequence[WindowSpec],
                live: Any):
    """Evaluate window functions (OverWindowFramesExec analog) scatter-free.

    Rows are sorted by (partition keys, order keys); all computations are cumulative
    scans + boundary gathers over the contiguous partition/peer runs.  Returns
    (order permutation, live_sorted, [(data, valid)] per spec) — outputs align to the
    SORTED order; the operator gathers payload columns with the same permutation."""
    n = live.shape[0]
    sort_keys = [(d, v, False, True) for d, v in part_keys] + list(order_keys)
    order = sort_indices(sort_keys, live)
    live_s = live[order]
    arange = jnp.arange(n, dtype=jnp.int64)

    def boundaries(keys):
        flag = jnp.zeros(n, dtype=jnp.bool_)
        for d, v in keys:
            # canonicalize NULLs: the data under an invalid slot is unspecified and
            # must not split the all-NULLs partition/peer run
            dc = d if v is None else jnp.where(v, d, jnp.zeros_like(d))
            d_s = dc[order]
            flag = flag | jnp.concatenate(
                [jnp.ones(1, jnp.bool_), d_s[1:] != d_s[:-1]])
            if v is not None:
                v_s = v[order]
                flag = flag | jnp.concatenate(
                    [jnp.zeros(1, jnp.bool_), v_s[1:] != v_s[:-1]])
        return flag.at[0].set(True)

    new_part = boundaries(part_keys) if part_keys else \
        jnp.zeros(n, jnp.bool_).at[0].set(True)
    new_run = new_part | (boundaries([(d, v) for d, v, _, _ in order_keys])
                          if order_keys else new_part)

    # per-row partition start / peer-run start (cummax of marked positions)
    part_start = jax.lax.cummax(jnp.where(new_part, arange, -1))
    run_start = jax.lax.cummax(jnp.where(new_run, arange, -1))
    # run/partition END per row: position before the NEXT boundary
    # dead rows sort to the global end; ends must stop at the last LIVE row or a
    # whole/range-frame gather would land on a dead padded slot
    n_live = jnp.sum(live_s.astype(jnp.int64))
    last_live = jnp.clip(n_live - 1, 0, n - 1)
    (starts_list,) = jnp.nonzero(new_run, size=n + 1, fill_value=n)
    run_ix = jnp.cumsum(new_run.astype(jnp.int64)) - 1
    run_end = jnp.clip(starts_list[jnp.clip(run_ix + 1, 0, n)] - 1, 0, n - 1)
    run_end = jnp.minimum(run_end, last_live)
    (pstarts_list,) = jnp.nonzero(new_part, size=n + 1, fill_value=n)
    part_ix = jnp.cumsum(new_part.astype(jnp.int64)) - 1
    part_end = jnp.clip(pstarts_list[jnp.clip(part_ix + 1, 0, n)] - 1, 0, n - 1)
    part_end = jnp.minimum(part_end, last_live)

    out = []
    for spec in specs:
        if spec.kind == "row_number":
            out.append(((arange - part_start + 1).astype(jnp.int64), None))
            continue
        if spec.kind == "rank":
            out.append(((run_start - part_start + 1).astype(jnp.int64), None))
            continue
        if spec.kind == "dense_rank":
            c = jnp.cumsum(new_run.astype(jnp.int64))
            dr = c - c[jnp.clip(part_start, 0, n - 1)] + 1
            out.append((dr.astype(jnp.int64), None))
            continue

        d, v = inputs[spec.arg]
        d_s = d[order]
        v_s = v[order] if v is not None else None
        present = live_s if v_s is None else (live_s & v_s)

        if spec.kind in ("lag", "lead"):
            idx = arange - spec.offset if spec.kind == "lag" else \
                arange + spec.offset
            in_part = (idx >= part_start) & (idx <= part_end)
            idxc = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
            data = d_s[idxc]
            valid = in_part & (present[idxc])
            out.append((data, valid))
            continue
        if spec.kind == "first_value":
            pos = jnp.clip(part_start, 0, n - 1).astype(jnp.int32)
            out.append((d_s[pos], present[pos]))
            continue
        if spec.kind == "last_value":
            pos = (run_end if spec.frame == "range" else
                   part_end if spec.frame == "whole" else arange)
            pos = jnp.clip(pos, 0, n - 1).astype(jnp.int32)
            out.append((d_s[pos], present[pos]))
            continue

        # aggregates over the frame
        if spec.kind == "count":
            masked = present.astype(jnp.int64)
        elif spec.kind == "sum":
            if jnp.issubdtype(d_s.dtype, jnp.floating):
                masked = jnp.where(present, d_s, jnp.zeros((), d_s.dtype))
            else:
                masked = jnp.where(present, d_s.astype(jnp.int64), 0)
        elif spec.kind in ("min", "max"):
            if jnp.issubdtype(d_s.dtype, jnp.floating):
                neutral = jnp.array(np.inf if spec.kind == "min" else -np.inf,
                                    d_s.dtype)
            else:
                info = jnp.iinfo(d_s.dtype)
                neutral = jnp.array(info.max if spec.kind == "min" else info.min,
                                    d_s.dtype)
            masked = jnp.where(present, d_s, neutral)
        else:
            raise ValueError(f"unknown window kind {spec.kind}")

        if spec.kind in ("min", "max"):
            running = _segmented_scan(masked, new_part, spec.kind == "min")
            nonempty_run = _segmented_scan(present.astype(jnp.int8), new_part,
                                           False) > 0
        else:
            c = jnp.cumsum(masked)
            base = jnp.where(part_start > 0,
                             c[jnp.clip(part_start - 1, 0, n - 1)], 0)
            running = c - base
            cp = jnp.cumsum(present.astype(jnp.int64))
            basep = jnp.where(part_start > 0,
                              cp[jnp.clip(part_start - 1, 0, n - 1)], 0)
            nonempty_run = (cp - basep) > 0

        pos = (run_end if spec.frame == "range" else
               part_end if spec.frame == "whole" else arange)
        pos = jnp.clip(pos, 0, n - 1).astype(jnp.int32)
        data = running[pos]
        if spec.kind == "count":
            out.append((data, None))
        else:
            out.append((data, nonempty_run[pos]))
    return order, live_s, out
