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


def _repartition_by_hash(lanes, live, hash_lane, quota):
    ns = jax.lax.axis_size(AXIS)
    n = live.shape[0]
    dest = ((hash_lane >> jnp.uint64(32)).astype(jnp.uint32)
            % jnp.uint32(ns)).astype(jnp.int32)
    # dead rows: send nowhere (dest stays, live=False travels with them)
    order = jnp.lexsort((jnp.arange(n), jnp.where(live, dest, ns)))
    dest_s = dest[order]
    live_s = live[order]
    counts = jnp.sum(jnp.where(live[None, :] & (dest[None, :] ==
                                                jnp.arange(ns)[:, None]), 1, 0),
                     axis=1)
    overflow = jnp.any(counts > quota)
    starts = jnp.searchsorted(jnp.where(live_s, dest_s, ns), jnp.arange(ns))
    rank = jnp.arange(n) - starts[jnp.clip(dest_s, 0, ns - 1)]
    ok = (rank >= 0) & (rank < quota) & live_s
    flat = jnp.where(ok, dest_s * quota + rank, ns * quota)

    out_lanes = []
    for lane in lanes:
        lane_s = lane[order]
        buf = jnp.zeros(ns * quota, dtype=lane.dtype)
        buf = buf.at[flat].set(jnp.where(ok, lane_s, jnp.zeros((), lane.dtype)),
                               mode="drop")
        x = jax.lax.all_to_all(buf.reshape(ns, quota), AXIS, 0, 0).reshape(-1)
        out_lanes.append(x)
    live_buf = jnp.zeros(ns * quota, dtype=jnp.bool_).at[flat].set(ok, mode="drop")
    live_x = jax.lax.all_to_all(live_buf.reshape(ns, quota), AXIS, 0, 0).reshape(-1)
    return out_lanes, live_x, overflow


def compact_rows(lanes: Sequence[Any], live: Any,
                 rows: int) -> Tuple[List[Any], Any]:
    """The shard's live rows moved, in their order, to the front of `rows`
    slots: what a join side goes through before it is exchanged, so that
    quotas and `cap` follow rows and not the slots a filter left empty.

    Returns (lanes [rows], live [rows]).  `rows` is at least the shard's live
    count (the caller read it), so nothing is dropped and no flag is needed.
    Gathers only: a running count of `live`, the position of the j-th live
    row by a search of that count, one gather a lane."""
    with jax.named_scope("exchange/compact"):
        run = jnp.cumsum(live, dtype=jnp.int32)
        nth = jnp.arange(1, rows + 1, dtype=jnp.int32)
        pos = jnp.minimum(jnp.searchsorted(run, nth, side="left"),
                          live.shape[0] - 1)
        return [lane[pos] for lane in lanes], nth <= run[-1]


def broadcast_all(lanes: Sequence[Any], live: Any) -> Tuple[List[Any], Any]:
    """Replicate every shard's rows to all shards (broadcast join build side).

    Returns lanes of shape [S*R] and the combined live mask."""
    with jax.named_scope("exchange/broadcast"):
        out = [jax.lax.all_gather(lane, AXIS, axis=0, tiled=False).reshape(
            (-1,) + lane.shape[1:]) for lane in lanes]
        live_g = jax.lax.all_gather(live, AXIS, axis=0,
                                    tiled=False).reshape(-1)
    return out, live_g

