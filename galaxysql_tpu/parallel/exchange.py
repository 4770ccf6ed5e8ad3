"""The exchange plane: repartition/broadcast as ICI collectives.

Reference analog: the MPP data plane — `PartitionedOutputBuffer`/`ExchangeClient`
shuttling LZ4 pages over HTTP (SURVEY.md §2.7, §5.8 plane 3).  Here an exchange is a
collective inside the SPMD program: hash repartition = bucketed `all_to_all`, broadcast
= `all_gather`, both over the mesh's `shard` axis (ICI inside a slice).  No serde, no
HTTP, no compression — the interconnect moves raw column lanes.

All functions run INSIDE shard_map blocks: arrays are the local shard ([R] lanes).
Fixed shapes: each destination gets a `quota`-sized bucket; senders report overflow so
the host can retry with a bigger quota (the reference's unbounded buffers become
bounded buckets + retry, consistent with the engine's overflow-retry discipline).
`compact_rows` is what a sparse join side goes through first, so that those shapes
follow its rows: it moves nothing between shards and cannot overflow.

A row's place is a running count, on every platform: its rank among the live rows
bound for its destination (`_rank_in_destination`), written there by scatters of
32-bit words (`_place`).  No sort, no gather through an order, and a search only
where `compact_rows` keeps under a 64th of its slots (`compact_path`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

AXIS = "shard"


def _cost(kind: str, lanes: Sequence[Any], slots: int) -> Dict[str, int]:
    return {kind + "_calls": len(lanes) + 1,
            kind + "_bytes": slots * (sum(x.dtype.itemsize for x in lanes) + 1),
            "slots": slots}


def repartition_cost(lanes: Sequence[Any], quota: int) -> Dict[str, int]:
    """What `repartition_by_hash(lanes, ..., quota)` moves, from static shapes
    alone (call it where the exchange is traced): one `all_to_all` a lane plus
    one for `live`, each handing over ONE shard's send buffer of `S * quota`
    slots, of which `(S - 1) / S` leave the chip."""
    return _cost("all_to_all", lanes, jax.lax.axis_size(AXIS) * quota)


def broadcast_cost(lanes: Sequence[Any], rows: int) -> Dict[str, int]:
    """What `broadcast_all` of `rows`-slot lanes moves: one `all_gather` a lane
    plus one for `live`.  Bytes and slots are those of ONE shard's gathered
    result (`S * rows` slots a lane), so that, as for the repartition,
    `(S - 1) / S` of them crossed the interconnect."""
    return _cost("all_gather", lanes, jax.lax.axis_size(AXIS) * rows)


def repartition_by_hash(lanes: Sequence[Any], live: Any, hash_lane: Any,
                        quota: int) -> Tuple[List[Any], Any, Any]:
    """Hash-repartition rows over the mesh axis.

    lanes: per-row payload arrays [R]; live: [R] bool; hash_lane: uint64 [R].
    Returns (exchanged lanes [S*quota], exchanged live, overflow flag scalar).
    Row r goes to shard (hash >> 32) % S; each (src, dst) pair carries `quota`
    slots.  The HIGH word, because tables are partitioned on the low bits of
    the same mix (`meta/catalog.hash_partition_of`): from the low bits a join
    on the partition key would send every row of a shard to one destination,
    S times the uniform share that the quotas are sized for.
    """
    with jax.named_scope("exchange/repartition"):
        return _repartition_by_hash(lanes, live, hash_lane, quota)


def _rank_in_destination(live, dest, ns: int):
    """Where the exchange puts a row: (`rank` int32 [n], `counts` int32 [ns]).
    `rank` of a live row is the number of live rows before it, in slot order,
    that are bound for the same destination (`dest` int32 [n], in `[0, ns)`);
    `counts[d]` is the live rows bound for `d`.  A dead row's rank is 0 and
    means nothing.  `ns` running counts and a select: no sort, no search."""
    rank = jnp.zeros(live.shape[0], jnp.int32)
    counts = []
    for d in range(ns):
        here = live & (dest == d)
        run = jnp.cumsum(here, dtype=jnp.int32)
        rank = jnp.where(here, run - 1, rank)
        counts.append(run[-1])
    return rank, jnp.stack(counts)


def _place(lane, slot, slots: int):
    """`lane` written ONCE, from where it lies, into a zeroed buffer of `slots`
    slots: row r to `slot[r]` (int32, distinct where inside the buffer), a row
    whose slot is `slots` or more nowhere.  A 64-bit lane goes as its two
    32-bit words: a scatter of 32-bit updates costs 5 ns an update on a v5e,
    one of 64-bit updates 70-114 (PERF.md section 6, PR 36)."""
    def put(words):
        return jnp.zeros(slots, words.dtype).at[slot].set(words, mode="drop")

    if lane.dtype.itemsize != 8:
        return put(lane)
    bits = jax.lax.bitcast_convert_type(lane, jnp.uint64)
    low = put(bits.astype(jnp.uint32))
    high = put((bits >> jnp.uint64(32)).astype(jnp.uint32))
    return jax.lax.bitcast_convert_type(
        high.astype(jnp.uint64) << jnp.uint64(32) | low.astype(jnp.uint64),
        lane.dtype)


def _repartition_by_hash(lanes, live, hash_lane, quota):
    ns = jax.lax.axis_size(AXIS)
    dest = ((hash_lane >> jnp.uint64(32)).astype(jnp.uint32)
            % jnp.uint32(ns)).astype(jnp.int32)
    # a live row's slot in the send buffer: its destination's bucket, at its
    # rank there; dead rows and those past the quota go nowhere
    rank, counts = _rank_in_destination(live, dest, ns)
    slot = jnp.where(live & (rank < quota), dest * quota + rank, ns * quota)
    out_lanes = [jax.lax.all_to_all(_place(lane, slot, ns * quota).reshape(ns, quota),
                                    AXIS, 0, 0).reshape(-1) for lane in lanes]
    # the live slots of a bucket are its first rows: written from the counts
    live_buf = (jnp.arange(quota, dtype=jnp.int32)[None, :]
                < jnp.minimum(counts, quota)[:, None])
    live_x = jax.lax.all_to_all(live_buf, AXIS, 0, 0).reshape(-1)
    return out_lanes, live_x, jnp.any(counts > quota)


# `compact_rows` keeps `rows` of `n` slots: from this many slots a kept one, the
# kept rows are searched for and not scattered.  Of 2,097,152 slots (v5e, PERF.md
# section 6, PR 36): 65,536 kept 15.4 ms by the search and 14.7 by the scatter,
# 16,384 kept 5.0 and 12.1, 1,024 kept 1.9 and 12.0.
SEARCH_FROM = 64


def compact_path(n: int, rows: int) -> str:
    """Which way `compact_rows` finds the `rows` slots it keeps of `n`."""
    return "search" if rows * SEARCH_FROM <= n else "scatter"


def compact_rows(lanes: Sequence[Any], live: Any,
                 rows: int) -> Tuple[List[Any], Any]:
    """The shard's live rows moved, in their order, to the front of `rows`
    slots: what a join side goes through before it is exchanged, so that
    quotas and `cap` follow rows and not the slots a filter left empty.

    Returns (lanes [rows], live [rows]).  `rows` is at least the shard's live
    count (the caller read it), so nothing is dropped and no flag is needed;
    where it is not, the first `rows` live rows are kept.  A live row's place
    is its rank among the live rows, a repartition's with one destination; ONE
    scatter puts the slot ids in their places and each lane is gathered
    through them, `rows` words a lane: scattering the lanes themselves would
    pay all `n` updates a lane, which costs more from `rows = n / 2` down.
    The id scatter pays its `n` updates whatever `rows` is, so a sparse side
    (`compact_path`, from the two static shapes) has the j-th live row
    searched for in the running count instead, `rows * log2(n)` words.  The
    slots behind the live rows are dead and hold zeros in every lane (a
    validity lane too)."""
    with jax.named_scope("exchange/compact"):
        n = live.shape[0]
        run = jnp.cumsum(live, dtype=jnp.int32)
        if compact_path(n, rows) == "search":
            nth = jnp.arange(1, rows + 1, dtype=jnp.int32)
            pos = jnp.minimum(jnp.searchsorted(run, nth), n - 1)
        else:
            pos = jnp.zeros(rows, jnp.int32).at[
                jnp.where(live, run - 1, rows)].set(
                    jnp.arange(n, dtype=jnp.int32), mode="drop")
        keep = jnp.arange(rows, dtype=jnp.int32) < run[-1]
        return [jnp.where(keep, lane[pos], jnp.zeros((), lane.dtype))
                for lane in lanes], keep


def broadcast_all(lanes: Sequence[Any], live: Any) -> Tuple[List[Any], Any]:
    """Replicate every shard's rows to all shards (broadcast join build side).

    Returns lanes of shape [S*R] and the combined live mask."""
    with jax.named_scope("exchange/broadcast"):
        out = [jax.lax.all_gather(lane, AXIS, axis=0, tiled=False).reshape(
            (-1,) + lane.shape[1:]) for lane in lanes]
        live_g = jax.lax.all_gather(live, AXIS, axis=0,
                                    tiled=False).reshape(-1)
    return out, live_g

