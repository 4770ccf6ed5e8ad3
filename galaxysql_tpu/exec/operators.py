"""Physical operators over ColumnBatches — the chunk engine.

Reference analog: `polardbx-executor/.../executor/operator` (SURVEY.md §2.6).  The shape of the
engine mirrors the reference's push/pull hybrid (`Executor.nextChunk` / `ConsumerExecutor.
consumeChunk`): streaming operators transform one batch at a time; blocking operators
(`HashAggOp`, `HashJoinOp` build, `SortOp`) consume all input then produce.  What differs is the
compute substrate: every hot loop is a jitted fixed-shape XLA program from
`kernels/relational.py`, and dynamic cardinality is handled by capacity buckets + overflow-retry
instead of growable hash maps (SURVEY.md §7.3).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galaxysql_tpu.chunk.batch import (Column, ColumnBatch, Dictionary, concat_batches,
                                       dictionary_translation)
from galaxysql_tpu.expr import ir
from galaxysql_tpu.expr.compiler import ExprCompiler, batch_env, _find_dictionary, \
    _signed_div_round, _pow10
from galaxysql_tpu.exec.programs import PROGRAMS
from galaxysql_tpu.exec.runtime_filter import RF_STATS
from galaxysql_tpu.kernels import relational as K
from galaxysql_tpu.runtime import exec_platform
from galaxysql_tpu.types import datatype as dt

MIN_BUCKET = 1024


def bucket_capacity(n: int) -> int:
    """Round up to a padding bucket (bounded recompile count, like chunk-size
    bucketing): powers of two up to 64K, then quarter-steps {1, 1.25, 1.5,
    1.75}x2^k.  Above 64K the finer ladder caps padding waste at 25% (a 1.2M-row
    scan would otherwise pad to 2M and every kernel pays 1.75x) while only 4x-ing
    the distinct compile shapes, all served by the persistent XLA cache."""
    c = MIN_BUCKET
    while c < n:
        c *= 2
    if c <= (1 << 16) or c == n:
        return c
    half = c // 2
    for q in (5, 6, 7):
        step = half + (half // 4) * (q - 4)
        if n <= step:
            return step
    return c


_JIT_CACHE: "collections.OrderedDict[Tuple, Any]" = collections.OrderedDict()
_JIT_CACHE_LOCK = __import__("threading").Lock()
_JIT_CACHE_LIMIT = 4096

# per-batch dispatch accounting (benchmarks/metrics/ap_dispatches_per_stmt.py,
# tp_dispatches_per_op.py and the tests' dispatch guards read it): every
# streaming-program invocation on one batch bumps `dispatches` — FilterOp,
# ProjectOp, a fused segment, the HashAgg partial, and the per-node MPP
# filter/project/agg programs each count 1 per batch.  A "dispatch" is one
# program-boundary crossing: an XLA dispatch on the device path, a host-np
# program call on the TP path (no jax dispatch there, but the same
# per-operator Python boundary the fuser removes).  Plain int adds: no device
# sync, no lock (approximate under concurrency, exact under one client).
DISPATCH_STATS = {"dispatches": 0}

# XLA trace+compile accounting: `retraces` counts global_jit builder runs
# (cache misses — each is a fresh program trace), `compile_ms` accumulates the
# wall time of each fresh program's FIRST invocation, which is where jax
# synchronously traces + compiles before dispatching.  Host-side plain adds;
# benchmarks/metrics/programs_compiled.py, compile_s.py and
# compiles_in_window.py read them, the statement summary snapshots them per
# query, and traced queries get one `compile` span per event.
COMPILE_STATS = {"retraces": 0, "compile_ms": 0.0, "cache_hits": 0}


# the sorted (TPU) join, one add per probe batch: `probes`, and where pairs
# were enumerated the passes the expansion's running maximum took
# (`kernels/relational._expand_rows`: none where no probe row has two pairs)
# against the levels a search of the whole probe lane runs for a pair slot.
# (The range lookup sorts and searches nothing: it has no depth to count.)
# The slot-table (CPU) formulation loops over nothing and adds nothing.
JOIN_STATS = {"probes": 0, "expand_levels": 0, "expand_full_depth_levels": 0,
              # equi-joins that went through `HashJoinOp._device_probe`, by the
              # plan's kind, once a join whatever its batches and its ladder
              # took; and the runs of a pair program that overflowed their
              # capacity and ran again (benchmarks/harness/local_joins.py)
              "inner": 0, "left": 0, "semi": 0, "anti": 0, "cap_climbs": 0}

# Where a join's capacity ladder settled: the key of its first rung (the
# join's keys, its sides' slots, the capacity twice its live probe rows gave)
# -> the capacity that held its pairs.  The next statement with that key
# starts there, so a join whose pairs outnumber twice its probe rows (a left
# join from the one side into the many) climbs once a process and not once a
# statement, as `parallel/mpp.py:_SETTLED` does for the mesh.  Only a ladder
# that climbed is kept.
_SETTLED_CAPS: Dict[tuple, int] = {}
_SETTLED_CAPS_LIMIT = 4096

# The same for an aggregate's group capacity: the key of its first rung (its
# keys and calls, the capacity the planner's estimate gave) -> the capacity
# that held its groups.  A climb re-iterates the CHILD (Q13's 100,000
# customers with an order against an estimate of 13,000: the left join below
# ran three times a statement), so it happens once a process and not once a
# statement.
_SETTLED_GROUPS: Dict[tuple, int] = {}


def _settle(memo: Dict[tuple, int], first_key: tuple, rung: int):
    if len(memo) >= _SETTLED_CAPS_LIMIT:
        memo.clear()
    memo[first_key] = rung


def reset_dispatch_stats():
    DISPATCH_STATS["dispatches"] = 0


def reset_compile_stats():
    COMPILE_STATS["retraces"] = 0
    COMPILE_STATS["compile_ms"] = 0.0
    COMPILE_STATS["cache_hits"] = 0


def program_family(key) -> str:
    """The family of a `global_jit` key: its first element (`join_pairs`,
    `agg_partial`, `mpp_agg`, ...); fused segments, whose key starts with the
    backend (exec/fusion.py), are `segment`.  Names hold the family only —
    never a shape or a capacity — so a program keeps its name from run to run."""
    head = key[0] if isinstance(key, tuple) and key else "program"
    if head in ("jnp", "np"):
        return "segment"
    return str(head).replace("-", "_")


_BUILDING = __import__("threading").local()


def jit_program(fn, **jit_kwargs):
    """What a `global_jit` builder calls in place of `jax.jit`: names the
    function after the family of the key being built, so its HLO module reads
    `jit_join_pairs` and not `jit_run` in a device profile, then jits it.  A
    builder that returns two programs names both."""
    family = getattr(_BUILDING, "family", None)
    if family is None:
        raise RuntimeError("jit_program called outside a global_jit builder")
    fn.__name__ = fn.__qualname__ = family
    return jax.jit(fn, **jit_kwargs)  # galaxylint: disable=jit-raw -- the one sanctioned jit: every builder comes through here


def _family_scoped(key, builder):
    """`builder` run with the key's family bound for `jit_program` (restored
    after, since a builder may reach another `global_jit`)."""
    family = program_family(key)

    def scoped():
        prev = getattr(_BUILDING, "family", None)
        _BUILDING.family = family
        try:
            return builder()
        finally:
            _BUILDING.family = prev
    return scoped


def _timed_first_call(key, f, persist=True):
    """Wrap a freshly built program so its first invocation — where jax pays
    the synchronous trace+compile — is timed into COMPILE_STATS, recorded in
    the program registry (`exec/programs.py`: family, key digest, the call's
    abstract signature, the span that launched it) and, when a query is
    being traced, recorded as a `compile` span attributed to the active span.
    After the first call the bare program is swapped back into _JIT_CACHE so
    steady-state dispatches pay no wrapper frame; callers still holding the
    wrapper degrade to a single cell-load per call."""
    import time as _t
    cell = [None]
    family = program_family(key)

    def wrapper(*a, **k):
        inner = cell[0]
        if inner is not None:
            return inner(*a, **k)
        from galaxysql_tpu.utils import tracing as _tr
        tc = _tr.current()
        launcher = tc.span_at_cursor() if tc is not None else None
        # while a profiler session records the statement, the trace+compile
        # is a real span in both trees; otherwise an event after the fact
        sp = tc.begin(f"compile:{family}", kind="compile") \
            if tc is not None and tc.annotate else None
        t0 = _t.perf_counter()
        try:
            out = f(*a, **k)
        finally:
            if sp is not None:
                tc.end(sp)
        dt_ms = (_t.perf_counter() - t0) * 1000.0
        cell[0] = f
        with _JIT_CACHE_LOCK:
            if _JIT_CACHE.get(key) is wrapper:
                _JIT_CACHE[key] = f
        COMPILE_STATS["compile_ms"] += dt_ms
        program = PROGRAMS.record(
            key, family, a, k, dt_ms,
            unsigned="" if persist and hasattr(f, "lower") else "a host closure",
            span=_tr._annotation_name(launcher.name, launcher.kind)
            if launcher is not None else "",
            trace_id=tc.trace_id if tc is not None else 0)
        if persist and program.signature is not None:
            # the same signature lets Instance.save AOT-serialize this
            # program into the persistent compile cache (no-op detached)
            from galaxysql_tpu.exec import compile_cache as _cc
            _cc.GLOBAL_COMPILE_CACHE.observe(key, f, program.signature)
        if sp is not None:
            sp.attrs.update(wall_ms=round(dt_ms, 3), program=program.digest)
        elif tc is not None:
            tc.event(f"compile:{family}", kind="compile",
                     wall_ms=round(dt_ms, 3), program=program.digest)
        return out

    return wrapper


def global_jit(key: Tuple, builder, built_flag=None, persist=True):
    """Process-wide LRU cache of jitted operator kernels.

    Operator instances are rebuilt per execution (plans are immutable, contexts are
    not), but the compiled XLA programs must survive across executions — otherwise a
    plan-cache hit still pays a full retrace+recompile.  Keys are semantic: expression
    tree keys plus the identity AND size of every dictionary whose contents are baked
    into the closure (a grown dictionary invalidates).

    Eviction is LRU one-at-a-time (move-to-end on hit, evict oldest on
    overflow) — a full clear at the limit would thundering-herd every hot query
    into a simultaneous retrace+recompile.  `built_flag`, when given, is called
    iff the builder actually ran (compile-vs-cached observability for tracing).
    Builder runs also feed COMPILE_STATS + the active trace's compile spans.

    On an in-memory miss, the persistent AOT cache (exec/compile_cache.py) is
    consulted first: a disk hit restores the compiled executable WITHOUT a
    retrace (counted as COMPILE_STATS['cache_hits']) — how a restarted
    coordinator skips the compile storm.  `persist=False` opts a program out
    (host-np closures that cannot serialize and would only churn lookups)."""
    with _JIT_CACHE_LOCK:
        f = _JIT_CACHE.get(key)
        if f is not None:
            _JIT_CACHE.move_to_end(key)
            return f
    builder = _family_scoped(key, builder)
    if persist:
        from galaxysql_tpu.exec import compile_cache as _cc
        g = _cc.GLOBAL_COMPILE_CACHE
        if g.attached:
            f = g.load(key, builder)
            if f is not None:
                with _JIT_CACHE_LOCK:
                    if key not in _JIT_CACHE:
                        _make_room()
                        _JIT_CACHE[key] = f
                        PROGRAMS.record(key, program_family(key), (), {}, 0.0,
                                        unsigned="restored ahead of time")
                    else:
                        f = _JIT_CACHE[key]
                    _JIT_CACHE.move_to_end(key)
                return f
    f = builder()
    if persist:
        # persist=False marks host-side np closures: rebuilding one costs
        # microseconds and compiles nothing, so it is not a retrace — the
        # counter tracks the XLA trace+compile storms the AOT cache exists
        # to eliminate.
        COMPILE_STATS["retraces"] += 1
    if callable(f):
        f = _timed_first_call(key, f, persist=persist)
    if built_flag is not None:
        built_flag()
    with _JIT_CACHE_LOCK:
        if key not in _JIT_CACHE:
            _make_room()
        _JIT_CACHE[key] = f
        _JIT_CACHE.move_to_end(key)
    return f


def _make_room():
    """Evict the oldest programs (and their registry entries) until one more
    fits; the caller holds `_JIT_CACHE_LOCK`."""
    while len(_JIT_CACHE) >= _JIT_CACHE_LIMIT:
        PROGRAMS.evict(_JIT_CACHE.popitem(last=False)[0])


def _dict_sig(e: ir.Expr) -> Tuple:
    """(uid, len) of every dictionary reachable from the expression.  uid is
    never reused (unlike id()), so a GC'd dictionary cannot alias a cache entry."""
    out = []
    for n in ir.walk(e):
        d = getattr(n, "dictionary", None)
        if d is not None:
            out.append((d.uid, len(d)))
    return tuple(out)


def expr_cache_key(e: ir.Expr) -> Tuple:
    return (e.key(), _dict_sig(e))


def lifted_keys(lift, exprs: Sequence[ir.Expr]):
    """Value-independent cache keys for `exprs` under `lift`, or None when any
    expression's masking is ambiguous (caller bakes values instead)."""
    keys = []
    for e in exprs:
        tk = lift.template_key(e)
        if tk is None:
            return None
        keys.append((tk, _dict_sig(e)))
    return tuple(keys)


# -- cross-session batched point lookup (server/batch_scheduler.py) -----------
#
# The mega-batched TP serving path: B parameter keys from concurrent sessions
# stack into ONE runtime argument of one jitted program per partition, instead
# of B separate index probes each paying its own dispatch + Python machinery
# (the Tailwind launch/transfer amortization case).  Programs key on STATIC
# batch-bucket sizes (`_BATCH_KEY_BUCKETS`) and the capacity-ladder-padded
# partition size, so steady-state traffic never retraces — only a genuinely
# new (bucket, capacity, dtype) shape compiles.

_BATCH_KEY_BUCKETS = (1, 4, 16, 64, 256, 1024)
BATCH_MAX_KEYS = _BATCH_KEY_BUCKETS[-1]
BATCH_MAXDUP = 8  # in-program cap on physical versions per key (overflow -> host)


def batch_key_bucket(n: int) -> int:
    """Smallest static key-batch bucket holding n keys (jit-shape ladder)."""
    for b in _BATCH_KEY_BUCKETS:
        if n <= b:
            return b
    return BATCH_MAX_KEYS


def _lane_pad_value(dtype: np.dtype):
    """A sort-order-maximal pad for sorted key lanes (pads never match a real
    searchsorted window because their MVCC stamps mark them dead anyway)."""
    if np.issubdtype(dtype, np.floating):
        return np.inf
    return np.iinfo(dtype).max


def _batched_point_program(B: int, cap: int, maxdup: int, dtype_str: str):
    """One jitted program: B keys against a capacity-padded sorted key lane.

    Inputs (all runtime args — values never bake into the trace):
      skeys[cap]  sorted key lane, padded with the dtype max
      sbegin[cap] begin_ts permuted to sorted order; NULL-key rows and pads
                  carry -1 (never visible)
      send[cap]   end_ts permuted to sorted order, pads 0 (dead)
      keys[B]     the stacked parameter keys (pad slots ignored by the host)
      snap, txn   0-d int64 arrays (abstract scalars: no per-value retrace)
    Returns (pos[B, maxdup], overflow[B]): visible sorted-domain positions
    (-1 = none) in ascending row order per key, and a per-key flag when the
    equal-key window exceeded maxdup (host falls back for that key only)."""
    def build():
        def prog(skeys, sbegin, send, keys, snap, txn):
            lo = jnp.searchsorted(skeys, keys, side="left")
            hi = jnp.searchsorted(skeys, keys, side="right")
            pos = lo[:, None] + jnp.arange(maxdup)[None, :]
            in_rng = pos < hi[:, None]
            posc = jnp.minimum(pos, cap - 1)
            b = sbegin[posc]
            e = send[posc]
            # mirror native.visible_mask: committed-and-past-snapshot insert,
            # minus committed-and-past-snapshot delete, plus own provisional
            ins = ((b >= 0) & (b <= snap)) | (b == -txn)
            dele = ((e >= 0) & (e <= snap)) | (e == -txn)
            vis = in_rng & ins & ~dele
            return jnp.where(vis, posc, -1), (hi - lo) > maxdup
        return jit_program(prog)
    return global_jit(("batch_point", dtype_str, B, cap, maxdup), build)


def _tail_windows(lane, n0: int, n: int, keys):
    """Sorted probe of the unsorted appended tail rows [n0, n): returns
    (torder, tlo, thi) — torder[tlo[i]:thi[i]] + n0 are key i's candidate
    row ids, in ascending row order (stable argsort).  Shared by the host
    and device batched-point paths so their tail handling stays
    bit-identical."""
    tail = lane[n0:n]
    torder = np.argsort(tail, kind="stable")
    tsorted = tail[torder]
    tlo = np.searchsorted(tsorted, keys, side="left")
    thi = np.searchsorted(tsorted, keys, side="right")
    return torder, tlo, thi


def _host_batched_point(part, col: str, lane_vals, snap: int, txn_id: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """XLA:CPU formulation of the batched point lookup: one vectorized numpy
    sweep over the sorted key index for ALL keys (same backend-adaptive
    doctrine as `kernels.relational.prefer_scatter` — on CPU the per-call jax
    dispatch costs more than the whole probe).  Caller holds `part.lock`.
    Bit-identical CSR to the device program path."""
    from galaxysql_tpu import native
    k = len(lane_vals)
    n = part.num_rows
    lane = part.lanes[col]
    valid = part.valid[col]
    begin, end = part.begin_ts, part.end_ts
    n0, perm, skeys = part.key_index(col)
    keys = np.asarray(lane_vals).astype(lane.dtype)
    lo = np.searchsorted(skeys, keys, side="left")
    hi = np.searchsorted(skeys, keys, side="right")
    if n > n0:
        # unsorted appended tail: extend each key's candidate set
        torder, tlo, thi = _tail_windows(lane, n0, n, keys)
    else:
        tlo = thi = np.zeros(k, dtype=np.int64)
    reps = (hi - lo) + (thi - tlo)
    total = int(reps.sum())
    offsets = np.zeros(k + 1, dtype=np.int64)
    if total == 0:
        return np.zeros(0, dtype=np.int64), offsets
    # flatten every key's sorted-window (+ tail-window) positions in one shot:
    # within a key, index-window ids (ascending rows) come first, tail ids
    # (all >= n0) after — exactly key_candidates' ordering
    per_key = []
    for i in range(k):
        ids = perm[lo[i]:hi[i]]
        if thi[i] > tlo[i]:
            tids = torder[tlo[i]:thi[i]] + n0
            ids = np.concatenate([ids, tids]) if ids.size else tids
        per_key.append(ids)
    flat = np.concatenate(per_key)
    keep = valid[flat] & native.visible_mask(begin[flat], end[flat],
                                             snap, txn_id)
    key_of = np.repeat(np.arange(k), reps)[keep]
    np.cumsum(np.bincount(key_of, minlength=k), out=offsets[1:])
    return flat[keep], offsets


def batched_point_lookup(store, pid: int, part, col: str, version: int,
                         lane_vals, snap: int, txn_id: int = 0,
                         device_cache=None, force_device: bool = False
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Visible row ids of `col == v` for a stack of keys against one
    partition, resolved by ONE jitted dispatch over the sorted key index
    (device backends), or one vectorized host sweep (XLA:CPU, where the
    dispatch itself would dominate — `force_device` pins the program path).

    Returns CSR (ids, offsets): ids[offsets[i]:offsets[i+1]] are key i's
    matching row ids, ascending — bit-identical to the sequential
    key_candidates + validity + visible_mask path.  The capacity-padded
    sorted artifacts (keys / permuted MVCC stamps) are version-keyed through
    `device_cache` (the DeviceCache lane budget) so steady-state flushes ship
    only the B keys; the unsorted appended tail and >BATCH_MAXDUP version
    pileups are probed host-side per flush."""
    from galaxysql_tpu import native
    k = len(lane_vals)
    with part.lock:
        if not force_device and exec_platform() == "cpu":
            return _host_batched_point(part, col, lane_vals, snap, txn_id)
        n = part.num_rows
        lane = part.lanes[col]
        valid = part.valid[col]
        begin, end = part.begin_ts, part.end_ts
        n0, perm, skeys = part.key_index(col)
        cap = bucket_capacity(max(n0, 1))
        B = batch_key_bucket(k)
        pad = _lane_pad_value(lane.dtype)
        keys = np.full(B, pad, dtype=lane.dtype)
        keys[:k] = np.asarray(lane_vals).astype(lane.dtype)

        def _pad(arr, fill):
            if arr.shape[0] == cap:
                return arr
            out = np.full(cap, fill, dtype=arr.dtype)
            out[:arr.shape[0]] = arr
            return out

        def build_keys():
            return _pad(skeys, pad)

        def build_begin():
            # NULL key slots fold into the begin stamp (-1 = never visible):
            # the sequential path's part.valid[col] filter, one array early
            return _pad(np.where(valid[:n0][perm], begin[:n0][perm],
                                 np.int64(-1)), np.int64(-1))

        def build_end():
            return _pad(end[:n0][perm], np.int64(0))

        if device_cache is not None:
            # the cached artifacts are materializations of THIS sorted-index
            # build, so the key must carry the index identity (lane_gen, n0)
            # as well as the table version: key_index() can rebuild with a
            # larger n0 within one version (tail growth past _INDEX_TAIL
            # mid-statement), and a (version, cap)-only hit would then map
            # stale sorted positions through the fresh perm — wrong rows
            sig = f"{col}::{part.lane_gen}.{n0}"
            dk = device_cache.get_lane_built(store, pid, f"bp_keys::{sig}",
                                             version, cap, build_keys)
            db = device_cache.get_lane_built(store, pid, f"bp_begin::{sig}",
                                             version, cap, build_begin)
            de = device_cache.get_lane_built(store, pid, f"bp_end::{sig}",
                                             version, cap, build_end)
        else:
            dk, db, de = build_keys(), build_begin(), build_end()
        prog = _batched_point_program(B, cap, BATCH_MAXDUP, str(lane.dtype))
        DISPATCH_STATS["dispatches"] += 1
        pos, overflow = prog(dk, db, de, keys,
                             np.int64(snap), np.int64(txn_id))
        pos = np.asarray(pos)[:k]
        overflow = np.asarray(overflow)[:k]

        # fast path: no appended tail, no version-pileup overflow — flatten
        # the position matrix in one shot (row-major keeps per-key ascending)
        mask = pos >= 0
        counts = mask.sum(axis=1)
        if n == n0 and not overflow.any():
            offsets = np.zeros(k + 1, dtype=np.int64)
            np.cumsum(counts, out=offsets[1:])
            return perm[pos[mask]], offsets

        per_key: List[np.ndarray] = [perm[row[row >= 0]] for row in pos]
        if n > n0:
            # unsorted appended tail: one vectorized sorted probe for all keys
            torder, tlo, thi = _tail_windows(lane, n0, n, keys[:k])
            for i in np.nonzero(thi > tlo)[0]:
                tids = torder[tlo[i]:thi[i]] + n0
                keep = valid[tids] & native.visible_mask(
                    begin[tids], end[tids], snap, txn_id)
                tids = tids[keep]
                if tids.size:
                    per_key[i] = np.concatenate([per_key[i], tids]) \
                        if per_key[i].size else tids
        for i in np.nonzero(overflow)[0]:
            # >BATCH_MAXDUP physical versions: exact host probe for this key
            ids = part.key_candidates(col, lane_vals[i])
            keep = valid[ids] & native.visible_mask(begin[ids], end[ids],
                                                    snap, txn_id)
            per_key[i] = ids[keep]
        offsets = np.zeros(k + 1, dtype=np.int64)
        np.cumsum(np.asarray([a.size for a in per_key]), out=offsets[1:])
        flat = (np.concatenate(per_key) if offsets[-1]
                else np.zeros(0, dtype=np.int64))
        return flat, offsets


def _is_host_batch(b: ColumnBatch) -> bool:
    """True when every lane is host numpy (TP scans yield these): small point
    queries then run the np expression backend directly — per-call jax dispatch
    (~0.5ms) dwarfs the actual work at point-query sizes."""
    for c in b.columns.values():
        if not isinstance(c.data, np.ndarray):
            return False
    live = b.live
    return live is None or isinstance(live, np.ndarray)


TP_HOST_ROWS = 1 << 16


def broadcast_value(n: int, data, valid, xp=jnp):
    """Materialize a compiled (data, valid) pair to full row length.

    Scalars appear when an expression is constant (literals, NULL); data and valid
    broadcast independently — e.g. `col + NULL` has full-length data but scalar
    valid.  `xp` picks the backend: jnp inside jitted programs (default), np for
    the host expression path (fused segments run both)."""
    if not hasattr(data, "shape") or data.shape == ():
        data = xp.broadcast_to(xp.asarray(data), (n,))
    if valid is not None and (not hasattr(valid, "shape") or valid.shape == ()):
        valid = xp.broadcast_to(xp.asarray(valid), (n,))
    return data, valid


@dataclasses.dataclass
class AggCall:
    kind: str                    # sum | count | avg | min | max | count_star
    arg: Optional[ir.Expr]       # None for count_star
    name: str
    distinct: bool = False

    @property
    def dtype(self) -> dt.DataType:
        if self.kind in ("count", "count_star"):
            return dt.BIGINT
        at = self.arg.dtype
        if self.kind == "sum":
            if at.clazz == dt.TypeClass.DECIMAL:
                return dt.decimal(18, at.scale)
            if at.clazz == dt.TypeClass.FLOAT:
                return dt.DOUBLE
            return dt.BIGINT
        if self.kind == "avg":
            if at.clazz == dt.TypeClass.DECIMAL:
                return dt.decimal(18, min(at.scale + 4, 8))
            return dt.DOUBLE
        return at  # min/max


class Operator:
    """Pull-model operator: iterate ColumnBatches."""

    output_schema: Dict[str, dt.DataType]

    def batches(self) -> Iterator[ColumnBatch]:
        raise NotImplementedError


class SourceOp(Operator):
    def __init__(self, batches: Iterable[ColumnBatch]):
        # materialize one-shot iterators: blocking operators (agg overflow retry)
        # re-iterate their children
        self._batches = batches if isinstance(batches, (list, tuple)) \
            else list(batches)

    def batches(self) -> Iterator[ColumnBatch]:
        yield from self._batches


class FilterOp(Operator):
    """WHERE: ANDs the predicate into the live mask (selection-vector style)."""

    def __init__(self, child: Operator, predicate: ir.Expr):
        self.child = child
        self.predicate = predicate

    def _compiled(self):
        from galaxysql_tpu.expr.compiler import LiftedLiterals
        lift = LiftedLiterals([self.predicate])
        tkeys = lifted_keys(lift, [self.predicate])
        if tkeys is None:
            lift = None

        def build():
            pred = ExprCompiler(jnp, lift=lift).compile_predicate(self.predicate)

            def run(batch: ColumnBatch, lits):
                env = batch_env(batch)
                env["$lits"] = lits
                # return the MASK only: passing columns through the jit would
                # make them XLA outputs, copying every lane (50MB/column at
                # SF1) — the caller reattaches the ORIGINAL column buffers
                return batch.live_mask() & pred(env)
            return jit_program(run)
        key = ("filter", tkeys if tkeys is not None
               else expr_cache_key(self.predicate))
        return global_jit(key, build), (lift.values() if lift is not None else ())

    def _compiled_np(self):
        from galaxysql_tpu.expr.compiler import LiftedLiterals
        lift = LiftedLiterals([self.predicate])
        tkeys = lifted_keys(lift, [self.predicate])
        if tkeys is None:
            lift = None

        def build():
            pred = ExprCompiler(np, lift=lift).compile_predicate(self.predicate)

            def run(batch: ColumnBatch, lits) -> ColumnBatch:
                env = {n: (c.data, c.valid) for n, c in batch.columns.items()}
                env["$lits"] = lits
                mask = np.broadcast_to(np.asarray(pred(env)),
                                       (batch.capacity,))
                live = batch.live if batch.live is not None else \
                    np.ones(batch.capacity, np.bool_)
                return ColumnBatch(batch.columns, live & mask)
            return run
        key = ("filter-np", tkeys if tkeys is not None
               else expr_cache_key(self.predicate))
        # host-np closure: nothing to AOT-serialize, skip persistent lookups
        return global_jit(key, build, persist=False), \
            (lift.values() if lift is not None else ())

    def batches(self) -> Iterator[ColumnBatch]:
        f = lits = fnp = None
        for b in self.child.batches():
            DISPATCH_STATS["dispatches"] += 1
            if b.capacity <= TP_HOST_ROWS and _is_host_batch(b):
                if fnp is None:
                    fnp, lits_np = self._compiled_np()
                yield fnp(b, lits_np)
                continue
            if f is None:
                f, lits = self._compiled()
            yield ColumnBatch(b.columns, f(b, lits))


class ProjectOp(Operator):
    """SELECT expressions; preserves the live mask."""

    def __init__(self, child: Operator, exprs: Sequence[Tuple[str, ir.Expr]]):
        self.child = child
        self.exprs = list(exprs)

    def _compiled(self):
        from galaxysql_tpu.expr.compiler import LiftedLiterals
        es = [e for _, e in self.exprs]
        lift = LiftedLiterals(es)
        tkeys = lifted_keys(lift, es)
        if tkeys is None:
            lift = None

        def build():
            comp = ExprCompiler(jnp, lift=lift)
            fns = [(name, e, comp.compile(e)) for name, e in self.exprs]

            def run(batch: ColumnBatch, lits) -> ColumnBatch:
                env = batch_env(batch)
                env["$lits"] = lits
                cols = {}
                n = batch.capacity
                for name, e, f in fns:
                    data, valid = broadcast_value(n, *f(env))
                    cols[name] = Column(data, valid, e.dtype, _find_dictionary(e))
                return ColumnBatch(cols, batch.live)
            return jit_program(run)
        if tkeys is not None:
            key = ("project", tuple(n for n, _ in self.exprs), tkeys)
        else:
            key = ("project", tuple((n, expr_cache_key(e)) for n, e in self.exprs))
        return global_jit(key, build), (lift.values() if lift is not None else ())

    def _compiled_np(self):
        from galaxysql_tpu.expr.compiler import LiftedLiterals
        es = [e for _, e in self.exprs]
        lift = LiftedLiterals(es)
        tkeys = lifted_keys(lift, es)
        if tkeys is None:
            lift = None

        def build():
            comp = ExprCompiler(np, lift=lift)
            fns = [(name, e, comp.compile(e)) for name, e in self.exprs]

            def run(batch: ColumnBatch, lits) -> ColumnBatch:
                env = {n: (c.data, c.valid) for n, c in batch.columns.items()}
                env["$lits"] = lits
                cols = {}
                n = batch.capacity

                def bc(x):
                    return None if x is None else \
                        np.broadcast_to(np.asarray(x), (n,))
                for name, e, f in fns:
                    data, valid = f(env)
                    cols[name] = Column(bc(data), bc(valid), e.dtype,
                                        _find_dictionary(e))
                return ColumnBatch(cols, batch.live)
            return run
        if tkeys is not None:
            key = ("project-np", tuple(n for n, _ in self.exprs), tkeys)
        else:
            key = ("project-np",
                   tuple((n, expr_cache_key(e)) for n, e in self.exprs))
        # host-np closure: nothing to AOT-serialize, skip persistent lookups
        return global_jit(key, build, persist=False), \
            (lift.values() if lift is not None else ())

    def batches(self) -> Iterator[ColumnBatch]:
        f = lits = fnp = None
        for b in self.child.batches():
            DISPATCH_STATS["dispatches"] += 1
            if b.capacity <= TP_HOST_ROWS and _is_host_batch(b):
                if fnp is None:
                    fnp, lits_np = self._compiled_np()
                yield fnp(b, lits_np)
                continue
            if f is None:
                f, lits = self._compiled()
            yield f(b, lits)


class HashAggOp(Operator):
    """Grouped/global aggregation with streaming partials + final merge.

    Each input batch is partially aggregated on device (sort+segment kernels); partials are
    concatenated and merged in a final pass — the same partial/final split the reference's
    `HashAggExec` + MPP partial-agg rules use, which later doubles as the distributed merge.
    """

    def __init__(self, child: Operator, group_exprs: Sequence[Tuple[str, ir.Expr]],
                 aggs: Sequence[AggCall], max_groups: int = 1 << 16,
                 spill_threshold: int = 256 << 20, prelude=None,
                 mem_pool=None):
        self.child = child
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        self.max_groups = max_groups
        # partial-state bytes above this spill to disk (MemoryRevoker analog)
        self.spill_threshold = spill_threshold
        self.spilled_partials = 0
        # per-query memory pool: partial bytes charge it; exhaustion (or a
        # cross-query squeeze revoke) forces the spill path early
        self.mem_pool = mem_pool
        # fused streaming chain (exec/fusion.FusedSegment) applied INSIDE the
        # partial kernel: scan→filter→project→partial-agg is one XLA program,
        # one dispatch per batch instead of one per operator
        self.prelude = prelude

    # -- kernel plumbing ---------------------------------------------------

    def _partial_specs(self) -> Tuple[List[ir.Expr], List[Tuple[str, K.AggSpec]]]:
        """Decompose SQL aggs into kernel specs (avg -> sum + count)."""
        inputs: List[ir.Expr] = []
        index: Dict[Tuple, int] = {}

        def arg_ix(e: ir.Expr) -> int:
            k = e.key()
            if k not in index:
                index[k] = len(inputs)
                inputs.append(e)
            return index[k]

        lanes: List[Tuple[str, K.AggSpec]] = []
        for a in self.aggs:
            if a.kind == "count_star":
                lanes.append((a.name, K.AggSpec("count_star", -1)))
            elif a.kind == "count":
                lanes.append((a.name, K.AggSpec("count", arg_ix(a.arg))))
            elif a.kind == "sum":
                lanes.append((a.name, K.AggSpec("sum", arg_ix(a.arg))))
            elif a.kind == "avg":
                lanes.append((a.name + "$sum", K.AggSpec("sum", arg_ix(a.arg))))
                lanes.append((a.name + "$cnt", K.AggSpec("count", arg_ix(a.arg))))
            elif a.kind in ("min", "max"):
                lanes.append((a.name, K.AggSpec(a.kind, arg_ix(a.arg))))
            else:
                raise ValueError(a.kind)
        return inputs, lanes

    def _cache_key(self) -> Tuple:
        return (tuple((n, expr_cache_key(e)) for n, e in self.group_exprs),
                tuple((a.kind, a.name,
                       expr_cache_key(a.arg) if a.arg is not None else None)
                      for a in self.aggs))

    MATMUL_AGG_MAX_DOMAIN = 64

    def _matmul_domains(self) -> Optional[List[int]]:
        """Static key domains if the dense-slot agg formulations apply, else None.

        Eligible when every group key has a small statically known domain
        (dictionary string or boolean — dict codes are guaranteed < len(dict)).
        Global aggregation (no keys) is domain 1 and always eligible: it turns
        the lexsort into plain masked reductions.  Which dense-slot kernel runs
        (MXU one-hot matmul vs CPU scatter-add) is decided per-backend inside
        `K.groupby`; the matmul byte-limb path additionally rejects float SUMs
        there."""
        domains: List[int] = []
        total = 1
        for _n, e in self.group_exprs:
            if e.dtype.clazz == dt.TypeClass.BOOL:
                dom = 2
            elif e.dtype.is_string:
                d = _find_dictionary(e)
                if d is None or len(d) == 0:
                    return None
                dom = len(d)
            else:
                return None
            domains.append(dom)
            total *= dom + 1  # +1: a NULL slot may be added per nullable key
            if total > self.MATMUL_AGG_MAX_DOMAIN:
                return None
        return domains

    def _partial_fn(self, max_groups: int):
        domains = self._matmul_domains()
        prelude = self.prelude
        key = ("agg_partial", exec_platform(), self._cache_key(), max_groups,
               tuple(domains) if domains is not None else None,
               prelude.key() if prelude is not None else None)

        def build():
            papply = prelude.build_apply(jnp) if prelude is not None else None
            comp = ExprCompiler(jnp)
            gfns = [comp.compile(e) for _, e in self.group_exprs]
            inputs, lanes = self._partial_specs()
            ifns = []
            for e in inputs:
                f = comp.compile(e)
                # MIN/MAX on dictionary strings must compare collation ranks, not codes;
                # _finalize maps ranks back to codes (count is rank-insensitive)
                d_ = _find_dictionary(e) if e.dtype.is_string else None
                from galaxysql_tpu.types import collation as _coll
                if d_ is not None and len(d_) and (
                        not d_.is_sorted or
                        _coll.collation_of_expr(e) is not None):
                    rank = _coll.sort_rank_array(e, d_)

                    def ranked(env, _f=f, _r=rank):
                        dd, vv = _f(env)
                        return jnp.asarray(_r)[dd], vv
                    f = ranked
                ifns.append(f)
            specs = tuple(s for _, s in lanes)

            def run(batch: ColumnBatch, plits):
                env = batch_env(batch)
                live = batch.live_mask()
                if papply is not None:
                    env, live = papply(env, live, plits)
                n = batch.capacity
                keys = [broadcast_value(n, *f(env)) for f in gfns]
                ins = [broadcast_value(n, *f(env)) for f in ifns]
                # backend-adaptive: dense-slot (matmul/scatter) when domains are
                # small and static, hash (CPU) / lexsort (TPU) otherwise
                return K.groupby(keys, ins, specs, live, max_groups,
                                 domains)
            return jit_program(run)
        return global_jit(key, build)

    def _merge_fn(self, max_groups: int, n_keys: int, lane_names: Tuple[str, ...],
                  merge_specs: Tuple[K.AggSpec, ...]):
        # shared across ALL aggregations: behavior depends only on the merge specs and
        # capacity (key/agg lane dtypes are part of jit's own trace signature)
        key = ("agg_merge", exec_platform(), max_groups, n_keys, merge_specs)

        def build():
            def run(key_lanes, input_lanes, live):
                return K.groupby(key_lanes, input_lanes, merge_specs, live,
                                 max_groups)
            return jit_program(run)
        return global_jit(key, build)

    # -- execution ---------------------------------------------------------

    MAX_GROUPS_CEILING = 1 << 24

    def batches(self) -> Iterator[ColumnBatch]:
        inputs, lanes = self._partial_specs()
        lane_names = tuple(name for name, _ in lanes)
        first = (exec_platform(), self._cache_key(), self.max_groups,
                 self.prelude.key() if self.prelude is not None else None)
        mg = _SETTLED_GROUPS.get(first, self.max_groups)
        from galaxysql_tpu.exec.memory import PoolCharge
        from galaxysql_tpu.exec.spill import Spiller
        # capacity under-estimates retry the whole aggregation with doubled output
        # capacity (children re-iterate; scans re-read from the store)
        spiller = Spiller()
        charge = PoolCharge(self.mem_pool)
        try:
            while True:
                partials: List[K.GroupByResult] = []
                spiller.close()
                partial_bytes = 0
                charge.to(0)
                overflowed = False
                plits = self.prelude.lits() if self.prelude is not None else ()
                for b in self.child.batches():
                    f = self._partial_fn(mg)
                    DISPATCH_STATS["dispatches"] += 1
                    r = f(b, plits)
                    if bool(r.overflow):
                        overflowed = True
                        break
                    host = jax.tree.map(np.asarray, r)
                    partials.append(host)
                    partial_bytes += _groupby_result_bytes(host)
                    # spill when over the threshold, when the per-query pool
                    # cannot cover the resident partials, or when a revoker
                    # (memory governor / another query's reservation) asked
                    # this operator to give memory back
                    if partial_bytes > self.spill_threshold or \
                            not charge.to(partial_bytes) or charge.squeeze:
                        for p in partials:
                            spiller.spill(_groupby_result_to_arrays(p))
                        self.spilled_partials += len(partials)
                        partials = []
                        partial_bytes = 0
                        charge.to(0)
                        charge.squeeze = False
                if not overflowed:
                    break
                mg *= 2
                if mg > self.MAX_GROUPS_CEILING:
                    raise RuntimeError("group cardinality exceeds engine ceiling")
            if mg != self.max_groups:
                _settle(_SETTLED_GROUPS, first, mg)

            # hierarchical merge: consume spilled partials in threshold-bounded waves
            # so peak host memory stays ~spill_threshold + merged-state size
            out = self._merge_waves(partials, spiller, mg, inputs, lanes, lane_names)
            if out is not None:
                yield out
        finally:
            spiller.close()
            charge.close()



    def _merge_partials(self, parts: List[K.GroupByResult], mg: int,
                        lane_names, merge_specs) -> Tuple[K.GroupByResult, int]:
        """Merge a list of host partials into one; returns (result, possibly-grown mg)."""

        def cat(arrs):
            return np.concatenate(arrs) if arrs else np.zeros(0)

        key_lanes = []
        for i in range(len(self.group_exprs)):
            d = cat([np.asarray(p.keys[i][0]) for p in parts])
            vs = [p.keys[i][1] for p in parts]
            v = None if all(x is None for x in vs) else \
                np.concatenate([np.asarray(x) if x is not None else
                                np.ones(np.asarray(p.keys[i][0]).shape[0], np.bool_)
                                for x, p in zip(vs, parts)])
            key_lanes.append((jnp.asarray(d), None if v is None else jnp.asarray(v)))
        live = jnp.asarray(cat([np.asarray(p.live) for p in parts]).astype(np.bool_))
        agg_lanes = []
        for j in range(len(lane_names)):
            d = cat([np.asarray(p.aggs[j][0]) for p in parts])
            vs = [p.aggs[j][1] for p in parts]
            v = None if all(x is None for x in vs) else \
                np.concatenate([np.asarray(x) if x is not None else
                                np.ones(np.asarray(p.aggs[j][0]).shape[0], np.bool_)
                                for x, p in zip(vs, parts)])
            agg_lanes.append((jnp.asarray(d), None if v is None else jnp.asarray(v)))
        while True:
            f = self._merge_fn(mg, len(key_lanes), lane_names, merge_specs)
            r = f(tuple(key_lanes), tuple(agg_lanes), live)
            if not bool(r.overflow):
                return jax.tree.map(np.asarray, r), mg
            mg *= 2  # distinct groups across partials can exceed one partial's cap
            if mg > self.MAX_GROUPS_CEILING:
                raise RuntimeError("group cardinality exceeds engine ceiling")

    def _merge_waves(self, partials, spiller, mg, inputs, lanes,
                     lane_names) -> ColumnBatch:
        merge_specs = []
        for (name, spec) in lanes:
            if spec.kind in ("count", "count_star", "sum"):
                merge_specs.append(K.AggSpec("sum", len(merge_specs)))
            else:
                merge_specs.append(K.AggSpec(spec.kind, len(merge_specs)))
        merge_specs = tuple(merge_specs)

        if not partials and not spiller.spilled_files:
            if self.group_exprs:
                return None  # grouped agg over empty input: no rows at all
            empty = [(np.zeros(1, np.int64), np.zeros(1, np.bool_))
                     for _ in lane_names]
            r = K.GroupByResult(tuple(), tuple(empty), np.zeros(1, np.bool_),
                                np.int32(0), np.bool_(False))
            return self._finalize(r, lane_names)

        if len(partials) == 1 and not spiller.spilled_files:
            # single partial (the common fused-scan case): it IS the result —
            # partial and merge lane layouts coincide, skip the merge kernel
            # (finalize is pure host math; partials are already np)
            return self._finalize(partials[0], lane_names)

        acc: Optional[K.GroupByResult] = None
        wave: List[K.GroupByResult] = []
        wave_bytes = 0

        def flush():
            nonlocal acc, wave, wave_bytes, mg
            if not wave:
                return
            parts = ([acc] if acc is not None else []) + wave
            acc, mg = self._merge_partials(parts, mg, lane_names, merge_specs)
            wave = []
            wave_bytes = 0

        for d in spiller.read_all():
            p = _groupby_result_from_arrays(d)
            wave.append(p)
            wave_bytes += _groupby_result_bytes(p)
            if wave_bytes > self.spill_threshold:
                flush()
        for p in partials:
            wave.append(p)
            wave_bytes += _groupby_result_bytes(p)
            if wave_bytes > self.spill_threshold:
                flush()
        flush()
        return self._finalize(acc, lane_names)

    def _finalize(self, r: K.GroupByResult, lane_names: Tuple[str, ...]) -> ColumnBatch:
        """Materialize final output batch; avg = sum/count with MySQL decimal
        scale.  Pure host math over the (already host) partial result — no
        device round trips for what is a tiny per-group fix-up."""
        cols: Dict[str, Column] = {}
        for i, (name, ge) in enumerate(self.group_exprs):
            d, v = r.keys[i]
            cols[name] = Column(np.asarray(d),
                                None if v is None else np.asarray(v),
                                ge.dtype, _find_dictionary(ge))
        lanes = {n: r.aggs[j] for j, n in enumerate(lane_names)}
        n_groups_live = np.asarray(r.live)
        if not self.group_exprs and n_groups_live.shape[0]:
            # global aggregation always yields exactly one row
            n_groups_live = np.zeros_like(n_groups_live)
            n_groups_live[0] = True
        for a in self.aggs:
            if a.kind == "avg":
                s, sv = lanes[a.name + "$sum"]
                c, _ = lanes[a.name + "$cnt"]
                at = a.arg.dtype
                rt = a.dtype
                s = np.asarray(s)
                c = np.asarray(c)
                safe = np.where(c == 0, 1, c)
                if rt.clazz == dt.TypeClass.DECIMAL:
                    shift = rt.scale - (at.scale if at.clazz == dt.TypeClass.DECIMAL else 0)
                    num = s.astype(np.int64) * _pow10(max(shift, 0))
                    q = _signed_div_round(np, num, safe)
                    data = q
                else:
                    data = s.astype(np.float64) / safe
                    data = data.astype(np.float32)
                valid = (c > 0)
                cols[a.name] = Column(data, valid, rt, None)
            else:
                d, v = lanes[a.name]
                d = np.asarray(d)
                v = None if v is None else np.asarray(v)
                rt = a.dtype
                if a.kind == "sum" and rt.clazz == dt.TypeClass.FLOAT:
                    d = d.astype(np.float32)
                if a.kind in ("count", "count_star"):
                    v = None  # COUNT over empty group is 0, not NULL
                dict_ = _find_dictionary(a.arg) if (a.kind in ("min", "max") and
                                                    a.arg is not None and
                                                    a.arg.dtype.is_string) else None
                from galaxysql_tpu.types import collation as _coll
                if dict_ is not None and len(dict_) and (
                        not dict_.is_sorted or
                        _coll.collation_of_expr(a.arg) is not None):
                    # min/max ran on collation ranks; map winners back to codes
                    order = _coll.sort_order_array(a.arg, dict_)
                    ranks = np.clip(d, 0, len(order) - 1)
                    d = order[ranks]
                cols[a.name] = Column(d, v, rt, dict_)
        return ColumnBatch(cols, n_groups_live)


def _groupby_result_bytes(r: K.GroupByResult) -> int:
    total = 0
    for d, v in tuple(r.keys) + tuple(r.aggs):
        total += d.nbytes + (v.nbytes if v is not None else 0)
    return total + r.live.nbytes


def _groupby_result_to_arrays(r: K.GroupByResult) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {"live": np.asarray(r.live),
                                  "num_groups": np.asarray(r.num_groups),
                                  "overflow": np.asarray(r.overflow)}
    for i, (d, v) in enumerate(r.keys):
        out[f"k{i}_d"] = np.asarray(d)
        if v is not None:
            out[f"k{i}_v"] = np.asarray(v)
    for j, (d, v) in enumerate(r.aggs):
        out[f"a{j}_d"] = np.asarray(d)
        if v is not None:
            out[f"a{j}_v"] = np.asarray(v)
    return out


def _groupby_result_from_arrays(d: Dict[str, np.ndarray]) -> K.GroupByResult:
    keys = []
    i = 0
    while f"k{i}_d" in d:
        keys.append((d[f"k{i}_d"], d.get(f"k{i}_v")))
        i += 1
    aggs = []
    j = 0
    while f"a{j}_d" in d:
        aggs.append((d[f"a{j}_d"], d.get(f"a{j}_v")))
        j += 1
    return K.GroupByResult(tuple(keys), tuple(aggs), d["live"], d["num_groups"],
                           d["overflow"])


class HashJoinOp(Operator):
    """Equi hash join: build side fully materialized, probe side streamed.

    join_type: inner | left | semi | anti (probe side is the outer/left side).
    """

    def __init__(self, build: Operator, probe: Operator,
                 build_keys: Sequence[ir.Expr], probe_keys: Sequence[ir.Expr],
                 join_type: str = "inner",
                 residual: Optional[ir.Expr] = None,
                 build_schema: Optional[Dict[str, Tuple[dt.DataType,
                                                        Optional[Dictionary]]]] = None,
                 spill_threshold: int = 256 << 20,
                 enable_bloom: bool = True, probe_prelude=None,
                 rf_publish=None, rf_manager=None,
                 frag_cache=None, frag_key=None, frag_note=None,
                 skew_watch=None, mem_pool=None, output=None):
        assert join_type in ("inner", "left", "semi", "anti")
        # the columns the plan's parent reads of this join's output (None:
        # all of them); the fused tail of a join that is not a plain inner
        # one gathers no other lane at the pair slots
        self.output = output
        # filter-only fused segment (exec/fusion.FusedSegment) ANDed into the
        # probe live mask INSIDE the probe kernels: the WHERE above the probe
        # scan costs no separate program dispatch per batch.  Inner joins only:
        # left/semi/anti unmatched semantics read the probe mask on the host.
        assert probe_prelude is None or join_type == "inner"
        self.probe_prelude = probe_prelude
        self.build, self.probe = build, probe
        self.build_keys, self.probe_keys = list(build_keys), list(probe_keys)
        self.join_type = join_type
        self.residual = residual
        # build-side output schema, needed to null-extend when the build side is EMPTY
        # (otherwise the left-join output would be missing the build columns entirely)
        self.build_schema = build_schema
        # grace spill: a build side above this partitions BOTH sides by key
        # hash to disk and joins bucket pairs (HybridHashJoinExec analog)
        self.spill_threshold = spill_threshold
        self.grace_partitions = 0  # observable spill counter (tests)
        # passes of the sorted probes' expansions so far, against full depth
        self.expand_levels = self.expand_full_depth_levels = 0
        # per-query memory pool: accumulated build bytes charge it;
        # exhaustion or a squeeze revoke engages the grace path early
        self.mem_pool = mem_pool
        self.enable_bloom = enable_bloom  # NO_BLOOM hint disables runtime filters
        # planned runtime filters (exec/runtime_filter): once the build side
        # materializes, publish bloom/min-max filters for probe-side scans
        self.rf_publish = list(rf_publish or [])
        self.rf_manager = rf_manager
        # cross-query fragment cache (exec/fragment_cache): frag_key is the
        # build subtree's versioned fingerprint — a warm execution adopts the
        # cached build batch + CSR/native table + published filters and never
        # pulls the build operator; frag_note reports the hit (trace/ANALYZE)
        self.frag_cache = frag_cache
        self.frag_key = frag_key
        self.frag_note = frag_note
        # heavy-hitter runtime refresh (meta/statistics.observe_build_keys):
        # (TableMeta, column, field id) per build key that is a bare scan
        # column — the materialized build lane feeds the column's runtime
        # sketch so skew detection stays fresh between ANALYZE runs
        self.skew_watch = list(skew_watch or [])

    def _key_compilers(self):
        """Compile key pairs into a common lane domain.

        String keys from different dictionaries are aligned by translating probe codes into
        the build dictionary's code space (host-built table, applied as a device gather);
        absent strings map to -1, which matches no build code.
        """
        comp = ExprCompiler(jnp)
        bk, pk = [], []
        for be, pe in zip(self.build_keys, self.probe_keys):
            bf, pf = comp.compile(be), comp.compile(pe)
            if be.dtype.is_string and pe.dtype.is_string:
                db = _find_dictionary(be)
                dp = _find_dictionary(pe)
                if db is not None and dp is not None and db is not dp:
                    trans = dictionary_translation(db, dp)

                    def translated(env, _pf=pf, _t=trans):
                        d, v = _pf(env)
                        return jnp.asarray(_t)[d], v
                    pf = translated
            bk.append(bf)
            pk.append(pf)
        return bk, pk

    def _plits(self) -> Tuple:
        return self.probe_prelude.lits() if self.probe_prelude is not None else ()

    def _probe_live_np(self, pb: ColumnBatch) -> np.ndarray:
        """Host probe live mask with the prelude filter applied (np twin of
        the in-kernel composition; native/grace paths)."""
        if self.probe_prelude is None:
            return pb.np_live()
        return self.probe_prelude.run_live_np(pb)

    def _pairs_fn(self, cap: int):
        prelude = self.probe_prelude
        key = ("join_pairs", exec_platform(), cap,
               tuple(expr_cache_key(e) for e in self.build_keys),
               tuple(expr_cache_key(e) for e in self.probe_keys),
               prelude.key() if prelude is not None else None)

        def build_fn():
            papply = prelude.build_apply(jnp) if prelude is not None else None
            bk, pk = self._key_compilers()

            def run(build: ColumnBatch, probe: ColumnBatch, plits):
                benv, penv = batch_env(build), batch_env(probe)
                plive = probe.live_mask()
                if papply is not None:
                    _env, plive = papply(penv, plive, plits)
                bkeys = [f(benv) for f in bk]
                pkeys = [f(penv) for f in pk]
                return K.hash_join_pairs(bkeys, pkeys, build.live_mask(),
                                         plive, cap)
            return jit_program(run)
        return global_jit(key, build_fn)

    def _csr_host(self, build_batch: ColumnBatch):
        """Host-built slot CSR over the build side (CPU backend).

        The slot-id lane is computed on device (hash math shared with the
        probe kernel); the argsort + bincount run in numpy — XLA:CPU's
        comparator sort is ~12x slower and was the single largest cost of the
        whole join (the CSR is also reused across probe batches/retries)."""
        nb = build_batch.capacity
        M = 1 << max(4, int(nb * 4 - 1).bit_length())
        key = ("join_build_slots", exec_platform(), nb, M,
               tuple(expr_cache_key(e) for e in self.build_keys))

        def build_fn():
            bk, _ = self._key_compilers()

            def run(build: ColumnBatch):
                benv = batch_env(build)
                bkeys = [f(benv) for f in bk]
                return K.hash_join_build_slots(bkeys, build.live_mask(), M)
            return jit_program(run)
        s_b = np.asarray(global_jit(key, build_fn)(build_batch))
        perm = np.argsort(s_b, kind="stable").astype(np.int32)
        counts = np.bincount(s_b, minlength=M + 1)[:M].astype(np.int32)
        ends = np.cumsum(counts, dtype=np.int64)
        starts = (ends - counts).astype(np.int64)
        return (jnp.asarray(perm), jnp.asarray(starts), jnp.asarray(counts), M)

    def _probe_csr_fn(self, cap: int, M: int, nb: int):
        prelude = self.probe_prelude
        key = ("join_probe_csr", exec_platform(), cap, M, nb,
               tuple(expr_cache_key(e) for e in self.build_keys),
               tuple(expr_cache_key(e) for e in self.probe_keys),
               prelude.key() if prelude is not None else None)

        def build_fn():
            papply = prelude.build_apply(jnp) if prelude is not None else None
            bk, pk = self._key_compilers()

            def run(build: ColumnBatch, probe: ColumnBatch,
                    perm, slot_starts, slot_counts, plits):
                benv, penv = batch_env(build), batch_env(probe)
                plive = probe.live_mask()
                if papply is not None:
                    _env, plive = papply(penv, plive, plits)
                bkeys = [f(benv) for f in bk]
                pkeys = [f(penv) for f in pk]
                return K.hash_join_probe_csr(bkeys, pkeys, build.live_mask(),
                                             plive, perm,
                                             slot_starts, slot_counts, M, cap)
            return jit_program(run)
        return global_jit(key, build_fn)

    BLOOM_MAX_BUILD = 1 << 20

    def _build_bloom(self, build_batch: ColumnBatch, pf):
        """Runtime bloom over the build key; probe batches filter on device.

        CPU builds the filter on device too (byte-plane bloom via scatter-max:
        no bit packing, one flag byte per bloom bit) — the host round trip of
        the build columns plus the num_live sync cost more than the whole join
        there.  TPU keeps the native host build + packed-word device query
        (device scatters serialize on TPU)."""
        if K.prefer_scatter():
            return self._build_bloom_device(build_batch, pf)
        from galaxysql_tpu import native
        n_build = build_batch.num_live()
        # `BLOOM_MAX_BUILD` keeps a large side's lanes from crossing the host
        # link for a filter; a side `_materialize_build` compacted on the host
        # is there already, and without its filter the pair capacity starts
        # from every live probe row (at SF10 Q3's 1.5M orders under 60M rows
        # of `lineitem`: 134M pair slots for 0.3M pairs)
        on_host = all(isinstance(c.data, np.ndarray)
                      for c in build_batch.columns.values())
        if n_build == 0 or (n_build > self.BLOOM_MAX_BUILD and not on_host):
            return None
        be = self.build_keys[0]
        benv = {n: (c.np_data(), None if c.valid is None else c.np_valid())
                for n, c in build_batch.columns.items()}
        d, v = ExprCompiler(np).compile(be)(benv)
        live = build_batch.np_live()
        if v is not None:
            live = live & v
        keys = np.asarray(d)[live].astype(np.int64)
        nwords = 1
        while nwords < max(2 * keys.size // 8, 64):  # ~16 bits/key
            nwords *= 2
        words = native.bloom_build(keys, nwords)
        words_dev = jnp.asarray(words)
        # both keys: a string probe key is translated into the build key's
        # dictionary by the compiled `pf`
        key = ("bloom_query", nwords, expr_cache_key(be),
               expr_cache_key(self.probe_keys[0]))

        def build():
            def run(batch: ColumnBatch, words):
                # the MASK only: lanes passed out of a jit would be copied
                pd, pv = pf(batch_env(batch))
                pd, _ = broadcast_value(batch.capacity, pd, None)
                live2 = batch.live_mask() & K.bloom_query_device(
                    pd.astype(jnp.int64), words)
                if pv is not None:
                    # NULL keys never match an inner/semi join anyway
                    live2 = live2 & pv
                return live2
            return jit_program(run)
        query = global_jit(key, build)

        def apply(batch: ColumnBatch) -> ColumnBatch:
            return ColumnBatch(batch.columns, query(batch, words_dev))
        return apply

    BLOOM_DEVICE_MAX_BITS = 1 << 24

    def _build_bloom_device(self, build_batch: ColumnBatch, pf):
        # gate on LIVE rows, same as the host path: a small build padded to a
        # large capacity bucket (or gathered out of an upstream join, mostly
        # dead rows) must not silently skip the bloom.  Sizing also follows
        # the live count — the padding rows never set a bit.
        n_build = build_batch.num_live() if build_batch.capacity else 0
        if n_build == 0 or n_build > self.BLOOM_MAX_BUILD:
            return None
        be = self.build_keys[0]
        nbits = 1 << max(12, int(n_build * 16 - 1).bit_length())
        nbits = min(nbits, self.BLOOM_DEVICE_MAX_BITS)
        key = ("bloom_dev", nbits, expr_cache_key(be),
               expr_cache_key(self.probe_keys[0]))

        def build_fns():
            comp = ExprCompiler(jnp)
            bf = comp.compile(be)
            mask = jnp.uint64(nbits - 1)

            def bits(d):
                h = K._mix64(d.astype(jnp.int64).astype(jnp.uint64))
                return ((h & mask).astype(jnp.int32),
                        ((h >> jnp.uint64(32)) & mask).astype(jnp.int32))

            def build_flags(batch: ColumnBatch):
                env = batch_env(batch)
                d, v = bf(env)
                live = batch.live_mask()
                if v is not None:
                    live = live & v
                d, _ = broadcast_value(batch.capacity, d, None)
                b1, b2 = bits(d)
                drop = jnp.int32(nbits)
                b1 = jnp.where(live, b1, drop)
                b2 = jnp.where(live, b2, drop)
                flags = jnp.zeros(nbits, jnp.uint8)
                one = jnp.ones(batch.capacity, jnp.uint8)
                return flags.at[b1].max(one, mode="drop").at[b2].max(
                    one, mode="drop")

            def query(batch_cols_live, flags):
                batch, = batch_cols_live
                env = batch_env(batch)
                pd, pv = pf(env)
                pd, _ = broadcast_value(batch.capacity, pd, None)
                q1, q2 = bits(pd)
                hit = (flags[q1] & flags[q2]) > 0
                live2 = batch.live_mask() & hit
                if pv is not None:
                    live2 = live2 & pv
                return ColumnBatch(batch.columns, live2)

            return jit_program(build_flags), jit_program(query)
        build_flags, query = global_jit(key, build_fns)
        flags = build_flags(build_batch)

        def apply(batch: ColumnBatch) -> ColumnBatch:
            return query((batch,), flags)
        return apply

    # -- grace spill (HybridHashJoinExec analog) -----------------------------

    def _key_compilers_np(self):
        """Host twins of _key_compilers: key lanes in a common np domain."""
        comp = ExprCompiler(np)
        bk, pk = [], []
        for be, pe in zip(self.build_keys, self.probe_keys):
            bf, pf = comp.compile(be), comp.compile(pe)
            if be.dtype.is_string and pe.dtype.is_string:
                db = _find_dictionary(be)
                dp = _find_dictionary(pe)
                if db is not None and dp is not None and db is not dp:
                    trans = np.asarray(dictionary_translation(db, dp))

                    def translated(env, _pf=pf, _t=trans):
                        d, v = _pf(env)
                        return _t[np.clip(d, 0, _t.shape[0] - 1)], v
                    pf = translated
            bk.append(bf)
            pk.append(pf)
        return bk, pk

    @staticmethod
    def _np_bucket(batch: ColumnBatch, kfns, P: int) -> np.ndarray:
        """Per-row bucket id from the join-key hash (host)."""
        from galaxysql_tpu.meta.statistics import _mix64
        env = {n: (c.np_data(), None if c.valid is None else c.np_valid())
               for n, c in batch.columns.items()}
        h = None
        for f in kfns:
            d, v = f(env)
            d = np.broadcast_to(np.asarray(d), (batch.capacity,))
            lane = _mix64(d.astype(np.int64).astype(np.uint64))
            if v is not None:
                vv = np.broadcast_to(np.asarray(v), (batch.capacity,))
                lane = np.where(vv, lane, np.uint64(0xDEADBEEFCAFEBABE))
            h = lane if h is None else _mix64(
                h * np.uint64(31) + lane + np.uint64(0x9E3779B97F4A7C15))
        return (h & np.uint64(P - 1)).astype(np.int64)

    @staticmethod
    def _spill_split(batch: ColumnBatch, buckets: np.ndarray, P: int,
                     spillers, schema_out: dict):
        live = batch.np_live()
        for name, c in batch.columns.items():
            schema_out.setdefault(name, (c.dtype, c.dictionary))
        for p in range(P):
            sel = np.nonzero(live & (buckets == p))[0]
            if sel.size == 0:
                continue
            arrays = {}
            for name, c in batch.columns.items():
                arrays[f"d::{name}"] = c.np_data()[sel]
                if c.valid is not None:
                    arrays[f"v::{name}"] = c.np_valid()[sel]
            arrays["::n"] = np.asarray([sel.size])
            spillers[p].spill(arrays)

    @staticmethod
    def _rebuild(run: dict, schema: dict) -> ColumnBatch:
        n = int(run["::n"][0])
        cols = {}
        for name, (typ, d_) in schema.items():
            d = run[f"d::{name}"]
            v = run.get(f"v::{name}")
            cols[name] = Column(jnp.asarray(d),
                                None if v is None else jnp.asarray(v), typ, d_)
        return ColumnBatch(cols, jnp.ones(n, dtype=jnp.bool_))

    def _grace_batches(self, build_parts: List[ColumnBatch],
                       build_iter) -> Iterator[ColumnBatch]:
        """Partition BOTH sides by key hash into P disk buckets; join each
        bucket pair in memory.  Rows of one key land in one bucket on both
        sides, so per-bucket joins compose exactly — including left/anti
        unmatched semantics (a probe row can only ever match inside its own
        bucket).  Build batches stream straight into buckets — the collected
        prefix spills first, then the remainder one batch at a time."""
        from galaxysql_tpu.exec.spill import Spiller
        P = 16  # total build size is unknown mid-stream; bucket pairs that
        #         still exceed memory join in-memory (bounded recursion none)
        self.grace_partitions = P
        bk, pk = self._key_compilers_np()
        b_spill = [Spiller() for _ in range(P)]
        p_spill = [Spiller() for _ in range(P)]
        b_schema: dict = {}
        p_schema: dict = {}
        try:
            import itertools
            for bb in itertools.chain(build_parts, build_iter):
                self._spill_split(bb, self._np_bucket(bb, bk, P), P, b_spill,
                                  b_schema)
            for pb in self.probe.batches():
                if self.probe_prelude is not None:
                    pb = ColumnBatch(pb.columns, self._probe_live_np(pb))
                self._spill_split(pb, self._np_bucket(pb, pk, P), P, p_spill,
                                  p_schema)
            for p in range(P):
                p_runs = [self._rebuild(r, p_schema)
                          for r in p_spill[p].read_all()]
                if not p_runs and self.join_type in ("inner", "semi"):
                    continue
                b_runs = [self._rebuild(r, b_schema)
                          for r in b_spill[p].read_all()]
                inner = HashJoinOp(
                    SourceOp(b_runs), SourceOp(p_runs),
                    self.build_keys, self.probe_keys, self.join_type,
                    self.residual, self.build_schema,
                    spill_threshold=1 << 62)  # bucket pairs join in memory
                yield from inner.batches()
        finally:
            for s in b_spill + p_spill:
                s.close()

    # -- native CPU join (ParallelHashJoinExec.java:131-226 analog) ----------

    def _np_key_lanes(self, kfns, batch: ColumnBatch):
        env = {n: (c.np_data(), None if c.valid is None else c.np_valid())
               for n, c in batch.columns.items()}
        out = []
        for f in kfns:
            d, v = f(env)
            d = np.broadcast_to(np.asarray(d), (batch.capacity,))
            if v is not None:
                v = np.broadcast_to(np.asarray(v), (batch.capacity,))
            out.append((d, v))
        return out

    def _native_build(self, build_batch: ColumnBatch) -> dict:
        """Build-side state of the native CPU join — the reusable (and
        fragment-cacheable) half: key lanes, effective-live mask, and the
        chained-hash table."""
        from galaxysql_tpu import native
        bk, _pk = self._key_compilers_np()
        blanes = self._np_key_lanes(bk, build_batch)
        b_eff = build_batch.np_live()
        for _d, v in blanes:
            if v is not None:
                b_eff = b_eff & v
        # single integer-domain key (FK/PK joins, dictionary codes, dates,
        # scaled decimals): chain on the key lane itself — exact matches, no
        # hash materialization and no verification pass
        single_int = len(blanes) == 1 and \
            not np.issubdtype(blanes[0][0].dtype, np.floating)
        bh = None
        if single_int:
            table = native.join_build_k1(blanes[0][0], b_eff)
        else:
            for d, v in blanes:
                bh = native.hash_combine(bh, d, v)
            table = native.join_build(bh, b_eff)
        return {"blanes": blanes, "b_eff": b_eff, "single_int": single_int,
                "bh": bh, "table": table}

    def _native_batches(self, build_batch: ColumnBatch,
                        art=None) -> Iterator[ColumnBatch]:
        """CPU-backend join: the native chained-hash hot loop (galaxystore
        gx_join_build/probe) with vectorized numpy verification/gathers.

        The XLA formulations stay the TPU path; on a scalar core the chained
        probe walks the build table at L2 speed, which no scatter/sort
        reformulation matches.  Exact-key verification keeps 64-bit hash
        collisions harmless; NULL keys never match (effective-live masks)."""
        from galaxysql_tpu import native
        _bk, pk = self._key_compilers_np()
        nb = art.native if art is not None else None
        if nb is None:
            nb = self._native_build(build_batch)
            if art is not None:
                art.native = nb
                self._frag_store(art)
        blanes, b_eff = nb["blanes"], nb["b_eff"]
        single_int, bh, table = nb["single_int"], nb["bh"], nb["table"]
        res_np = ExprCompiler(np).compile_predicate(self.residual) \
            if self.residual is not None else None

        for pb in self.probe.batches():
            if RF_STATS["enabled"]:
                # RAW batch live, BEFORE the probe prelude — the same point
                # the device path counts at, so the bench delta metric is
                # comparable across backends
                RF_STATS["probe_rows"] += int(pb.np_live().sum())
            planes = self._np_key_lanes(pk, pb)
            p_live_mask = self._probe_live_np(pb)
            p_eff = p_live_mask
            for _d, v in planes:
                if v is not None:
                    p_eff = p_eff & v
            if single_int and \
                    not np.issubdtype(planes[0][0].dtype, np.floating):
                b_of, p_of = native.join_probe_k1(planes[0][0], p_eff, table)
            else:
                if single_int:  # float probe lane against int build: generic
                    bh = native.hash_combine(None, blanes[0][0], blanes[0][1])
                    table = native.join_build(bh, b_eff)
                    single_int = False
                ph = None
                for d, v in planes:
                    ph = native.hash_combine(ph, d, v)
                b_of, p_of = native.join_probe(ph, p_eff, bh, table)
                # exact-key verification (hash collisions filtered here)
                if b_of.size:
                    ver = np.ones(b_of.shape[0], dtype=np.bool_)
                    for (bd, _bv), (pd, _pv) in zip(blanes, planes):
                        ver &= bd[b_of] == pd[p_of]
                    if not ver.all():
                        b_of, p_of = b_of[ver], p_of[ver]
            n = b_of.shape[0]
            keep = None
            if res_np is not None and n:
                # residual evaluated over PLAIN n-sized gathers (the padded
                # output lanes are only built for inner/left below)
                env = {}
                for name, c in build_batch.columns.items():
                    env[name] = (c.np_data()[b_of],
                                 c.np_valid()[b_of] if c.valid is not None
                                 else None)
                for name, c in pb.columns.items():
                    env[name] = (c.np_data()[p_of],
                                 c.np_valid()[p_of] if c.valid is not None
                                 else None)
                keep = np.broadcast_to(np.asarray(res_np(env)), (n,))
            if self.join_type in ("semi", "anti"):
                matched = np.zeros(pb.capacity, dtype=np.bool_)
                sel = p_of if keep is None else p_of[keep]
                matched[sel] = True
                live = p_live_mask & (matched if self.join_type == "semi"
                                      else ~matched)
                yield ColumnBatch(pb.columns, live)
                continue
            cap = bucket_capacity(max(n, 1))

            def gather_padded(c: Column, idx) -> Column:
                # gather STRAIGHT into the bucket-padded buffer: a plain
                # fancy-index + pad_to would copy every lane twice
                src = c.np_data()
                data = np.zeros(cap, dtype=src.dtype)
                if n:
                    np.take(src, idx, out=data[:n])
                valid = None
                if c.valid is not None:
                    valid = np.zeros(cap, dtype=np.bool_)
                    if n:
                        np.take(c.np_valid(), idx, out=valid[:n])
                return Column(data, valid, c.dtype, c.dictionary)

            cols: Dict[str, Column] = {}
            for name, c in build_batch.columns.items():
                cols[name] = gather_padded(c, b_of)
            for name, c in pb.columns.items():
                cols[name] = gather_padded(c, p_of)
            live_out = np.zeros(cap, dtype=np.bool_)
            live_out[:n] = True if keep is None else keep
            yield ColumnBatch(cols, live_out)
            if self.join_type == "left":
                matched = np.zeros(pb.capacity, dtype=np.bool_)
                matched[p_of if keep is None else p_of[keep]] = True
                unmatched = p_live_mask & ~matched
                ncols: Dict[str, Column] = {}
                for name, c in build_batch.columns.items():
                    z = np.zeros(pb.capacity, dtype=c.np_data().dtype)
                    ncols[name] = Column(z, np.zeros(pb.capacity, np.bool_),
                                         c.dtype, c.dictionary)
                ncols.update(pb.columns)
                yield ColumnBatch(ncols, unmatched)

    @staticmethod
    def _gather(batch: ColumnBatch, idx, live) -> Dict[str, Column]:
        """One side's payload lanes at the pair indices.  The lanes that live
        on the device go through one named program (eager `lane[idx]` is six
        unnamed dispatches a lane); a host lane (a compacted build side) is
        gathered where it lives, by numpy, and never uploaded for it."""
        lanes = {n: (c.data, c.valid) for n, c in batch.columns.items()
                 if isinstance(c.data, jax.Array)}
        out = {}
        if lanes:
            key = ("join_gather", batch.capacity, int(idx.shape[0]),
                   tuple((n, str(d.dtype), v is not None)
                         for n, (d, v) in lanes.items()))

            def build():
                def run(lanes, idx):
                    return {n: (d[idx], None if v is None else v[idx])
                            for n, (d, v) in lanes.items()}
                return jit_program(run)
            out = global_jit(key, build)(lanes, idx)
        cols = {}
        for n, c in batch.columns.items():
            data, valid = out[n] if n in out else (
                c.data[idx], None if c.valid is None else c.valid[idx])
            cols[n] = Column(data, valid, c.dtype, c.dictionary)
        return cols

    # -- fragment cache (exec/fragment_cache) --------------------------------

    def _frag_entry_key(self):
        """Artifact identity: the build subtree's versioned fingerprint plus
        everything that shapes the stored state — backend (device batch form),
        native availability (CSR vs chained table), the build key exprs, and
        the ACTIVE filter-publish spec set (a RUNTIME_FILTER(OFF) run must
        not hand a filterless artifact to a filters-on execution)."""
        rf_sig = tuple(sorted((s.filter_id, tuple(sorted(s.kinds)))
                              for s in self.rf_publish))
        return ("join_build", self.frag_key.key, exec_platform(),
                bool(K.prefer_scatter()),
                tuple(expr_cache_key(e) for e in self.build_keys), rf_sig)

    def _frag_lookup(self):
        if self.frag_cache is None or self.frag_key is None:
            return None
        return self.frag_cache.get(self._frag_entry_key())

    def _frag_admit(self, build_batch: ColumnBatch):
        """Fresh artifact for a cold build (None when caching is off),
        capturing the runtime filters just published from this build."""
        if self.frag_cache is None or self.frag_key is None:
            return None
        from galaxysql_tpu.exec import fragment_cache as fc
        from galaxysql_tpu.exec import runtime_filter as _rf
        art = fc.BuildArtifact(batch=build_batch)
        art.rows = build_batch.capacity
        art.filters = _rf.capture_published(self.rf_manager, self.rf_publish)
        return art

    def _frag_store(self, art):
        from galaxysql_tpu.exec import fragment_cache as fc
        self.frag_cache.put(self._frag_entry_key(), art,
                            fc.artifact_nbytes(art), self.frag_key.tables,
                            kind="join_build", rows=art.rows)

    def _rf_publish_cached(self, art):
        from galaxysql_tpu.exec import runtime_filter as _rf
        _rf.publish_captured(self.rf_manager, self.rf_publish, art.filters)

    def _observe_skew(self, build_batch: ColumnBatch):
        from galaxysql_tpu.meta import statistics as _stats
        live = build_batch.np_live()
        for tm, colname, fid in self.skew_watch:
            c = build_batch.columns.get(fid)
            if c is None:
                continue
            mask = live if c.valid is None else (live & c.np_valid())
            _stats.observe_build_keys(tm, colname, c.np_data()[mask])

    def _materialize_build(self, parts: List[ColumnBatch]) -> ColumnBatch:
        """The build side in one batch.  Read to the host and compacted there
        (`concat_batches`), unless it is one batch whose lanes are on the
        device already, too large for the host to build even a bloom filter
        from (`BLOOM_MAX_BUILD` slots), in a bucket's worth of slots more than
        half of them live: compaction would buy it little, and its lanes (35 MB
        of `orders`, 100 MB of late `lineitem` rows at SF1) would cross the
        host link twice a statement for it.  Such a batch stays where it is."""
        if not K.prefer_scatter() and len(parts) == 1:
            b = parts[0]
            on_device = isinstance(b.live, (jax.Array, type(None))) and all(
                isinstance(c.data, jax.Array) for c in b.columns.values())
            if on_device and b.capacity > self.BLOOM_MAX_BUILD and \
                    b.capacity == bucket_capacity(b.capacity) and \
                    2 * b.num_live() > b.capacity:
                return b
        return concat_batches(parts)

    def _empty_build_batches(self) -> Iterator[ColumnBatch]:
        # empty build: inner/semi yield nothing; anti passes probe rows through;
        # left null-extends using the declared build schema
        for pb in self.probe.batches():
            if self.join_type in ("inner", "semi"):
                continue
            if self.join_type == "anti":
                yield pb
                continue
            ncols: Dict[str, Column] = {}
            for name, (typ, d_) in (self.build_schema or {}).items():
                z = jnp.zeros(pb.capacity, dtype=typ.lane)
                ncols[name] = Column(z, jnp.zeros(pb.capacity, jnp.bool_), typ, d_)
            ncols.update(pb.columns)
            yield ColumnBatch(ncols, pb.live)

    def batches(self) -> Iterator[ColumnBatch]:
        from galaxysql_tpu import native as _native
        art = self._frag_lookup()
        if art is not None:
            # warm path: build batch + CSR/native table + published filters
            # straight from the fragment cache — the build subplan never runs
            if self.frag_note is not None:
                self.frag_note(art)
            if self.rf_publish:
                self._rf_publish_cached(art)
            build_batch = art.batch
            if build_batch.capacity == 0:
                yield from self._empty_build_batches()
                return
            if K.prefer_scatter() and _native.AVAILABLE:
                yield from self._native_batches(build_batch, art)
                return
            yield from self._device_probe(build_batch, art, stored=True)
            return
        # accumulate the build side batch-by-batch; crossing the spill
        # threshold — or exhausting the per-query memory pool, or a squeeze
        # revoke — hands the ALREADY-collected prefix plus the still-unread
        # remainder to the grace path, so peak memory stays ~threshold (the
        # full build is never concatenated first)
        from galaxysql_tpu.exec.memory import PoolCharge
        build_parts: List[ColumnBatch] = []
        build_bytes = 0
        charge = PoolCharge(self.mem_pool)
        try:
            build_iter = iter(self.build.batches())
            for b in build_iter:
                build_parts.append(b)
                build_bytes += _batch_bytes(b)
                if build_bytes > self.spill_threshold:
                    # what the side will hold once materialized: its live
                    # rows (a side gathered out of an upstream join at its
                    # pair capacity is mostly dead slots: at SF10 Q5's 2.3M
                    # orders of a year arrive in 33.5M)
                    build_bytes = sum(
                        _batch_bytes(p) * p.num_live() // max(p.capacity, 1)
                        for p in build_parts)
                if build_bytes > self.spill_threshold or \
                        not charge.to(build_bytes) or charge.squeeze:
                    # grace spill: the build never materializes in one
                    # piece, so no filter is published (and nothing is
                    # cached) — absent filters pass everything
                    charge.to(0)
                    yield from self._grace_batches(build_parts, build_iter)
                    return
            build_batch = self._materialize_build(build_parts)
            # planned runtime filters publish HERE — before any probe pull, so
            # probe-side scans (lazy generators) see the filter on first batch.
            # An empty build publishes pass-NOTHING filters, never pass-all.
            if self.rf_publish:
                from galaxysql_tpu.exec import runtime_filter as _rf
                _rf.publish_from_batch(self.rf_manager, self.rf_publish,
                                       build_batch)
            if K.prefer_scatter() and build_batch.capacity:
                # CPU: every downstream build-side cost (CSR bincount domain,
                # slot table size M, verify gathers) scales with CAPACITY,
                # and a build side gathered out of an upstream join is mostly
                # dead rows — host-compact first (sub-ms at build sizes)
                build_batch = build_batch.compact()
            if self.skew_watch and build_batch.capacity and K.prefer_scatter():
                # heavy-hitter refresh from the lanes this pass just
                # materialized on the host; the TPU path skips (lanes are
                # device-resident and the refresh must never add a sync)
                self._observe_skew(build_batch)
            art = self._frag_admit(build_batch)
            if build_batch.capacity == 0:
                if art is not None:
                    self._frag_store(art)
                yield from self._empty_build_batches()
                return
            if K.prefer_scatter() and _native.AVAILABLE:
                yield from self._native_batches(build_batch, art)
                return
            if _is_host_batch(build_batch):
                build_batch = build_batch.pad_to(
                    bucket_capacity(build_batch.capacity))
            if art is not None:
                art.batch = build_batch  # cache the padded device form
            yield from self._device_probe(build_batch, art, stored=False)
        finally:
            charge.close()

    def _note_probe(self, npr: int, expand: int):
        """Count one probe of the sorted formulation that enumerated pairs,
        and its expansion's passes of the depth a search of `npr` probe slots
        runs; in a traced statement, write "passes of full depth" so far onto
        the operator's span (the cursor is this join's `op:Join` while its
        batches are pulled)."""
        JOIN_STATS["probes"] += 1
        expand_full = K.full_search_depth(npr)
        JOIN_STATS["expand_levels"] += expand
        JOIN_STATS["expand_full_depth_levels"] += expand_full
        self.expand_levels += expand
        self.expand_full_depth_levels += expand_full
        sp = self._span()
        if sp is not None:
            sp.attrs["expand_levels"] = \
                f"{self.expand_levels} of {self.expand_full_depth_levels}"

    @staticmethod
    def _span():
        """This join's `op:Join` span while its batches are pulled, if the
        statement is traced."""
        from galaxysql_tpu.utils import tracing as _tr
        tc = _tr.current()
        return tc.span_at_cursor() if tc is not None else None

    def _matched_fn(self, csr: bool, probe_slots: Optional[int]):
        """The semi or anti join without a residual: the probe batch's live
        mask after the join and the rows it keeps, in one program with no
        pair slot (`K.hash_join_matched`).  With `probe_slots`, the probe keys
        are looked up in that many slots, the live rows moved to the front,
        and not in the batch's own."""
        keep_matched = self.join_type == "semi"
        key = ("join_pairs", exec_platform(), "matched", keep_matched, csr,
               tuple(expr_cache_key(e) for e in self.build_keys),
               tuple(expr_cache_key(e) for e in self.probe_keys), probe_slots)

        def build_fn():
            bk, pk = self._key_compilers()

            def run(build: ColumnBatch, probe: ColumnBatch, csr_lanes):
                benv, penv = batch_env(build), batch_env(probe)
                bkeys = [f(benv) for f in bk]
                pkeys = [f(penv) for f in pk]
                plive = probe.live_mask()
                if csr_lanes is None:
                    matched = K.hash_join_matched(
                        bkeys, pkeys, build.live_mask(), plive, probe_slots)
                else:
                    perm, starts, counts = csr_lanes
                    matched = K.hash_join_matched_csr(
                        bkeys, pkeys, build.live_mask(), plive, perm, starts,
                        counts, starts.shape[0])
                live = plive & (matched if keep_matched else ~matched)
                return live, jnp.sum(live, dtype=jnp.int32)
            return jit_program(run)
        return global_jit(key, build_fn)

    def _tail_fn(self, build_batch: ColumnBatch, pb: ColumnBatch, cap: int):
        """What follows the pair enumeration of a join that is not a plain
        inner one, in one program of the gather's family: both sides' lanes at
        the pair slots, the residual on them, the probe rows with a pair that
        passed it, and from those the kind's output: the pairs (inner, left),
        the unmatched probe rows over ONE all-NULL lane a build column's type
        (left), or the probe batch's live mask (semi, anti; the lanes the
        residual does not read are gathered by nobody).  Returns `(pairs
        batch or None, probe-side live mask or None, rows under that mask or
        None, {dtype: NULL lane})`."""
        kind, residual = self.join_type, self.residual
        lanes_sig = tuple(
            tuple((n, str(c.data.dtype), c.valid is not None)
                  for n, c in b.columns.items()) for b in (build_batch, pb))
        # what the parent reads (all of it where the plan does not say, or
        # reads no column at all: a batch has its slots from its lanes)
        names = set(build_batch.columns) | set(pb.columns)
        wanted = names & self.output if self.output else names
        wanted = frozenset(wanted or names)
        key = ("join_gather", exec_platform(), kind,
               expr_cache_key(residual) if residual is not None else None,
               build_batch.capacity, pb.capacity, cap, lanes_sig,
               tuple(sorted(wanted)))

        def build_fn():
            residual_pred = (ExprCompiler(jnp).compile_predicate(residual)
                             if residual is not None else None)

            def at(batch: ColumnBatch, idx):
                return {n: Column(c.data[idx],
                                  None if c.valid is None else c.valid[idx],
                                  c.dtype, c.dictionary)
                        for n, c in batch.columns.items()}

            def run(build: ColumnBatch, probe: ColumnBatch, pairs: K.JoinPairs):
                cols = {**at(build, pairs.build_idx),
                        **at(probe, pairs.probe_idx)}
                live, matched = pairs.live, pairs.probe_matched
                if residual_pred is not None:
                    live = live & residual_pred(
                        batch_env(ColumnBatch(cols, pairs.live)))
                    # a probe row is matched by a pair that ALSO passed
                    matched = K.probe_matched_from(live, pairs.probe_starts,
                                                   pairs.probe_offsets)
                # a lane only the residual read is gathered for it and for
                # nobody else: what is not returned the compiler drops
                out = ColumnBatch({n: c for n, c in cols.items()
                                   if n in wanted}, live)
                if kind == "inner":
                    return out, None, None, {}
                plive = probe.live_mask()
                if kind == "left":
                    unmatched = plive & ~matched
                    nulls = {str(c.data.dtype): jnp.zeros(probe.capacity,
                                                          c.data.dtype)
                             for n, c in build.columns.items() if n in wanted}
                    nulls["valid"] = jnp.zeros(probe.capacity, jnp.bool_)
                    return (out, unmatched,
                            jnp.sum(unmatched, dtype=jnp.int32), nulls)
                kept = plive & (matched if kind == "semi" else ~matched)
                return None, kept, jnp.sum(kept, dtype=jnp.int32), {}
            return jit_program(run)
        return global_jit(key, build_fn)

    def _pairs(self, build_batch: ColumnBatch, pb: ColumnBatch, csr, plits):
        """The pair enumeration at a capacity that holds it, and that capacity
        and the runs that overflowed on the way: `(pairs, cap, climbs)`.  The
        first rung is twice the live probe rows, or where this join's ladder
        settled before (`_SETTLED_CAPS`); an overflowed run tells how many
        candidate pairs there are, so the next rung is the bucket that holds
        them and no rung between is built.  No answer is made from an
        overflowed run."""
        # with a probe prelude the count predates the fused WHERE (counting
        # the post-filter mask would cost the dispatch the fusion saves):
        # cap is conservative, overflow-retry semantics unchanged
        first_cap = bucket_capacity(max(pb.num_live() * 2, MIN_BUCKET))
        prelude = self.probe_prelude
        first = (exec_platform(), first_cap, build_batch.capacity, pb.capacity,
                 tuple(expr_cache_key(e) for e in self.build_keys),
                 tuple(expr_cache_key(e) for e in self.probe_keys),
                 prelude.key() if prelude is not None else None)
        cap, climbs = _SETTLED_CAPS.get(first, first_cap), 0
        while True:
            if csr is not None:
                perm, starts, counts, M = csr
                pairs = self._probe_csr_fn(cap, M, build_batch.capacity)(
                    build_batch, pb, perm, starts, counts, plits)
            else:
                pairs = self._pairs_fn(cap)(build_batch, pb, plits)
            over, expand = jax.device_get(  # one read, not two
                (pairs.overflow, pairs.expand_levels))
            if not bool(over):
                break
            climbs += 1
            cap = bucket_capacity(int(np.asarray(pairs.probe_offsets)[-1]))
        JOIN_STATS["cap_climbs"] += climbs
        if climbs:
            _settle(_SETTLED_CAPS, first, cap)
        if expand is not None:  # the sorted formulation's
            self._note_probe(pb.capacity, int(expand))
        return pairs, cap, climbs

    def _device_probe(self, build_batch: ColumnBatch, art,
                      stored: bool) -> Iterator[ColumnBatch]:
        kind = self.join_type
        # runtime bloom filter (reference: RuntimeFilterBuilderExec -> scan pushdown,
        # SURVEY.md §2.7): for inner/semi joins with one key, probe rows that cannot
        # match are masked out before pair enumeration.  Bloom-negative rows are
        # provably unmatched, so semantics are exact for inner/semi; left/anti must
        # keep unmatched rows and skip the filter.
        bloom_filter = None
        if self.enable_bloom and kind in ("inner", "semi") and \
                len(self.build_keys) == 1:
            _, pk = self._key_compilers()
            bloom_filter = self._build_bloom(build_batch, pk[0])

        csr = None
        if K.prefer_scatter():
            csr = art.csr if art is not None and art.csr is not None \
                else self._csr_host(build_batch)
        if art is not None and not stored:
            art.csr = csr
            self._frag_store(art)
        plits = self._plits()
        JOIN_STATS[kind] += 1
        sp = self._span()
        if sp is not None:
            sp.attrs.update(kind=kind, residual=int(self.residual is not None),
                            cap=0, climbs=0)
        # a semi or anti join without a residual asks only WHETHER a probe row
        # has a match: no pair is enumerated, so there is no capacity to climb
        matched_only = self.residual is None and kind in ("semi", "anti")
        plain_inner = self.residual is None and kind == "inner"
        cap_max = climbs = 0
        counted = []  # row counts, read once after the last batch is taken
        for pb in self.probe.batches():
            if RF_STATS["enabled"]:
                # probe rows REACHING the join (post scan-side runtime-filter
                # pruning, pre join-local bloom) — the bench delta metric;
                # gated so the default path pays no extra device sync
                RF_STATS["probe_rows"] += int(pb.num_live())
            if bloom_filter is not None:
                pb = bloom_filter(pb)
            if matched_only:
                # a probe side that a filter left mostly dead (Q4: 57K orders
                # of a quarter in 1,572,864 slots) is looked up in the bucket
                # its live rows fill: the lookup and the comparison of the
                # candidates pay by the slot
                slots = bucket_capacity(max(pb.num_live(), MIN_BUCKET))
                if csr is not None or 2 * slots > pb.capacity:
                    slots = None
                live, rows = self._matched_fn(csr is not None, slots)(
                    build_batch, pb, None if csr is None else csr[:3])
                counted.append(rows)
                if csr is None:
                    JOIN_STATS["probes"] += 1  # the sorted formulation's
                yield ColumnBatch(pb.columns, live)
                continue
            pairs, cap, climbed = self._pairs(build_batch, pb, csr, plits)
            cap_max, climbs = max(cap_max, cap), climbs + climbed
            if sp is not None:
                sp.attrs.update(cap=cap_max, climbs=climbs)
            if plain_inner:
                bcols = self._gather(build_batch, pairs.build_idx, pairs.live)
                pcols = self._gather(pb, pairs.probe_idx, pairs.live)
                yield ColumnBatch({**bcols, **pcols}, pairs.live)
                continue
            out, side_live, rows, nulls = self._tail_fn(build_batch, pb, cap)(
                build_batch, pb, pairs)
            if rows is not None:
                counted.append(rows)
            if kind in ("semi", "anti"):
                yield ColumnBatch(pb.columns, side_live)
                continue
            yield out
            if kind == "left":
                # null-extended unmatched probe rows: every build column of a
                # lane type shares that type's one all-NULL lane
                ncols = {name: Column(nulls[str(c.data.dtype)], nulls["valid"],
                                      c.dtype, c.dictionary)
                         for name, c in build_batch.columns.items()
                         if name in out.columns}
                ncols.update((name, c) for name, c in pb.columns.items()
                             if name in out.columns)
                yield ColumnBatch(ncols, side_live)
        if counted and sp is not None:
            # the consumer has taken every batch and dispatched its own work:
            # these scalars are read behind it and hold nothing up
            sp.attrs["unmatched" if kind == "left" else "matched"] = \
                int(sum(jax.device_get(counted)))


class CrossJoinOp(Operator):
    """Cartesian product with a SMALL materialized build side.

    Exists for the uncorrelated-scalar-subquery pattern (1-row aggregate cross-joined
    into the outer query, SURVEY.md Q11/Q15/Q22 shapes); guarded against large builds.
    """

    MAX_CELLS = 1 << 26

    def __init__(self, build: Operator, probe: Operator, scalar: bool = False,
                 build_schema=None):
        self.build = build
        self.probe = probe
        # scalar subquery semantics: empty build NULL-extends, >1 rows errors
        self.scalar = scalar
        self.build_schema = build_schema

    @staticmethod
    def _one_row_lanes(build: ColumnBatch, capacity: int) -> Dict[str, Column]:
        """A one-row build side (a scalar subquery's answer) as lanes of
        `capacity` slots, in one named program of the join's gather family:
        eager `broadcast_to` is two unnamed modules a column."""
        lanes = {n: (c.data, c.valid) for n, c in build.columns.items()}
        key = ("join_gather", exec_platform(), "one_row", capacity,
               tuple((n, str(d.dtype), v is not None)
                     for n, (d, v) in lanes.items()))

        def build_fn():
            def run(lanes):
                return {n: (jnp.broadcast_to(d[0], (capacity,)),
                            None if v is None
                            else jnp.broadcast_to(v[0], (capacity,)))
                        for n, (d, v) in lanes.items()}
            return jit_program(run)
        out = global_jit(key, build_fn)(lanes)
        return {n: Column(*out[n], c.dtype, c.dictionary)
                for n, c in build.columns.items()}

    def batches(self) -> Iterator[ColumnBatch]:
        build = concat_batches(list(self.build.batches()))
        nb = build.num_live() if build.capacity else 0
        if self.scalar and nb > 1:
            from galaxysql_tpu.utils.errors import TddlError
            raise TddlError("Subquery returns more than 1 row")
        if self.scalar and nb == 0:
            for pb in self.probe.batches():
                ncols = {}
                for name, (typ, d_) in (self.build_schema or {}).items():
                    z = jnp.zeros(pb.capacity, dtype=typ.lane)
                    ncols[name] = Column(z, jnp.zeros(pb.capacity, jnp.bool_),
                                         typ, d_)
                ncols.update(pb.columns)
                yield ColumnBatch(ncols, pb.live)
            return
        build = build.compact().pad_to(build.num_live()) if build.capacity else build
        nb = build.capacity
        for pb in self.probe.batches():
            if nb == 0:
                return  # empty build: cross join is empty
            if nb == 1:
                cols = self._one_row_lanes(build, pb.capacity)
                cols.update(pb.columns)
                yield ColumnBatch(cols, pb.live)
                continue
            if nb * pb.capacity > self.MAX_CELLS:
                raise RuntimeError("cross join too large")
            # expand: probe rows repeated nb times each
            pidx = jnp.repeat(jnp.arange(pb.capacity), nb)
            bidx = jnp.tile(jnp.arange(nb), pb.capacity)
            cols = {}
            for name, c in build.columns.items():
                cols[name] = Column(c.data[bidx],
                                    c.valid[bidx] if c.valid is not None else None,
                                    c.dtype, c.dictionary)
            for name, c in pb.columns.items():
                cols[name] = Column(c.data[pidx],
                                    c.valid[pidx] if c.valid is not None else None,
                                    c.dtype, c.dictionary)
            live = pb.live_mask()[pidx] & build.live_mask()[bidx]
            yield ColumnBatch(cols, live)


class SortOp(Operator):
    """ORDER BY [LIMIT]: in-memory sort, or external sorted-run merge when the
    input exceeds the spill threshold.

    External path (SpilledTopNExec / external-sort analog): each
    threshold-sized slab is sorted on device, compacted, and spilled as a
    sorted run of host arrays (output columns + precomputed comparison-coded
    key lanes); runs then stream through a bounded-memory chunked k-way merge
    (per-run chunk heads, safe-prefix cut at the smallest chunk-tail key, the
    prefix merged with one np.lexsort per wave)."""

    def __init__(self, child: Operator,
                 keys: Sequence[Tuple[ir.Expr, bool]],  # (expr, descending)
                 limit: Optional[int] = None, offset: int = 0,
                 spill_threshold: int = 256 << 20, mem_pool=None):
        self.child = child
        self.keys = list(keys)
        self.limit = limit
        self.offset = offset
        self.spill_threshold = spill_threshold
        self.spilled_runs = 0  # observable spill counter (tests, EXPLAIN)
        # per-query memory pool: slab bytes charge it; exhaustion or a
        # squeeze revoke flushes the slab into a sorted run early
        self.mem_pool = mem_pool

    def _compiled(self):
        from galaxysql_tpu.types import collation as _coll
        key = ("sort", tuple((expr_cache_key(e), desc,
                              _coll.collation_of_expr(e))
                             for e, desc in self.keys),
               self.limit, self.offset)

        def build():
            # bind to locals: the cached closure must NOT capture self (it would pin
            # the whole child operator tree in the process-global kernel cache)
            limit, offset = self.limit, self.offset
            comp = ExprCompiler(jnp)
            kfns = []
            for e, desc in self.keys:
                f = comp.compile(e)
                if e.dtype.is_string:
                    # dictionary codes are assignment-ordered, not collation-ordered:
                    # sort by the host-computed rank of each code
                    d_ = _find_dictionary(e)
                    from galaxysql_tpu.types import collation as _coll
                    if d_ is not None and len(d_) and (
                            not d_.is_sorted or
                            _coll.collation_of_expr(e) is not None):
                        rank = _coll.sort_rank_array(e, d_)

                        def ranked(env, _f=f, _r=rank):
                            dta, vld = _f(env)
                            return jnp.asarray(_r)[dta], vld
                        f = ranked
                kfns.append((f, desc))

            def run(batch: ColumnBatch) -> ColumnBatch:
                env = batch_env(batch)
                keys = []
                for f, desc in kfns:
                    d, v = f(env)
                    keys.append((d, v, desc, not desc))  # NULLs first asc, last desc
                order = K.sort_indices(keys, batch.live_mask())
                cols = {}
                for name, c in batch.columns.items():
                    cols[name] = Column(c.data[order],
                                        c.valid[order] if c.valid is not None else None,
                                        c.dtype, c.dictionary)
                live = batch.live_mask()[order]
                if limit is not None:
                    live = K.limit_mask(live, offset, limit)
                elif offset:
                    live = K.limit_mask(live, offset, batch.capacity)
                return ColumnBatch(cols, live)
            return jit_program(run)
        return global_jit(key, build)

    def batches(self) -> Iterator[ColumnBatch]:
        from galaxysql_tpu.exec.memory import PoolCharge
        from galaxysql_tpu.exec.spill import Spiller
        slab: List[ColumnBatch] = []
        slab_bytes = 0
        spiller = Spiller()
        charge = PoolCharge(self.mem_pool)
        run_meta: List[int] = []  # row count per spilled run
        try:
            for b in self.child.batches():
                slab.append(b)
                slab_bytes += _batch_bytes(b)
                if slab_bytes > self.spill_threshold or \
                        not charge.to(slab_bytes) or charge.squeeze:
                    self._spill_run(slab, spiller, run_meta)
                    slab = []
                    slab_bytes = 0
                    charge.to(0)
                    charge.squeeze = False
            if not run_meta:
                merged = concat_batches(slab)
                if merged.capacity == 0:
                    yield merged
                    return
                padded = merged.pad_to(bucket_capacity(merged.capacity))
                yield self._compiled()(padded)
                return
            if slab:
                self._spill_run(slab, spiller, run_meta)
            yield from self._merge_runs(spiller, run_meta)
        finally:
            spiller.close()
            charge.close()

    # -- external sort -------------------------------------------------------

    def _key_codes(self, batch: ColumnBatch) -> List[np.ndarray]:
        """Comparison-coded host key lanes: lexsort over them (major key first)
        reproduces sort_indices order — NULL placement as a leading lane, DESC
        via exact integer complement (~x) / float negation."""
        env = {n: (c.np_data(), None if c.valid is None else c.np_valid())
               for n, c in batch.columns.items()}
        comp = ExprCompiler(np)
        out: List[np.ndarray] = []
        for e, desc in self.keys:
            d, v = comp.compile(e)(env)
            d = np.broadcast_to(np.asarray(d), (batch.capacity,))
            if e.dtype.is_string:
                d_ = _find_dictionary(e)
                from galaxysql_tpu.types import collation as _coll
                if d_ is not None and len(d_) and (
                        not d_.is_sorted or
                        _coll.collation_of_expr(e) is not None):
                    d = _coll.sort_rank_array(e, d_)[np.clip(d, 0, len(d_) - 1)]
            nulls_first = not desc  # MySQL: NULLs first asc, last desc
            if v is None:
                nk = np.ones(batch.capacity, np.int8)
            else:
                vv = np.broadcast_to(np.asarray(v), (batch.capacity,))
                nk = np.where(vv, np.int8(1), np.int8(0))
            if not nulls_first:
                nk = np.int8(1) - nk
            if np.issubdtype(d.dtype, np.floating):
                dk = -d.astype(np.float64) if desc else d.astype(np.float64)
            else:
                di = d.astype(np.int64)
                dk = ~di if desc else di
            if v is not None:
                dk = np.where(np.broadcast_to(np.asarray(v), dk.shape), dk, 0)
            out.append(nk)
            out.append(dk)
        return out

    def _spill_run(self, slab: List[ColumnBatch], spiller, run_meta: List[int]):
        merged = concat_batches(slab)
        if merged.capacity == 0:
            return
        codes = self._key_codes(merged)
        live = merged.np_live()
        order = np.lexsort(tuple(reversed(codes)))
        order = order[live[order]]  # compact: spilled runs hold live rows only
        arrays: Dict[str, np.ndarray] = {}
        for i, k in enumerate(codes):
            arrays[f"k{i}"] = k[order]
        for name, c in merged.columns.items():
            arrays[f"d::{name}"] = c.np_data()[order]
            if c.valid is not None:
                arrays[f"v::{name}"] = c.np_valid()[order]
        # column dtypes/dictionaries survive OUTSIDE the npz (metadata, not lanes)
        self._run_schema = [(name, c.dtype, c.dictionary)
                            for name, c in merged.columns.items()]
        spiller.spill_mmap(arrays)
        run_meta.append(int(order.shape[0]))
        self.spilled_runs += 1

    @staticmethod
    def _tuple_le(ks: List[np.ndarray], bound: Tuple) -> np.ndarray:
        """Vectorized lexicographic (k0,k1,...) <= bound."""
        lt = np.zeros(ks[0].shape[0], dtype=bool)
        eq = np.ones(ks[0].shape[0], dtype=bool)
        for a, b in zip(ks, bound):
            lt = lt | (eq & (a < b))
            eq = eq & (a == b)
        return lt | eq

    def _merge_runs(self, spiller, run_meta: List[int]) -> Iterator[ColumnBatch]:
        # mmap-backed: only the pages each merge wave slices become resident,
        # so peak memory is ~CHUNK x runs, not the full input
        runs = [spiller.open_mmap(i) for i in range(len(run_meta))]
        nk = 2 * len(self.keys)
        heads = [0] * len(runs)
        sizes = run_meta
        emitted = 0  # rows streamed out so far (pre offset/limit windowing)
        stop_at = None if self.limit is None else self.offset + self.limit
        CHUNK = 65536

        while stop_at is None or emitted < stop_at:
            # chunk window per live run; the merge-safe bound is the SMALLEST
            # among unfinished runs' chunk-tail keys (rows <= bound cannot be
            # preceded by any unread row)
            windows = []
            bound = None
            for ri, r in enumerate(runs):
                if heads[ri] >= sizes[ri]:
                    continue
                end = min(heads[ri] + CHUNK, sizes[ri])
                windows.append((ri, end))
                if end < sizes[ri]:
                    tail = tuple(r[f"k{i}"][end - 1] for i in range(nk))
                    if bound is None or tail < bound:
                        bound = tail
            if not windows:
                break
            take: List[Tuple[int, int, int]] = []  # (run, lo, hi)
            for ri, end in windows:
                lo = heads[ri]
                if bound is None:
                    hi = end
                else:
                    ks = [runs[ri][f"k{i}"][lo:end] for i in range(nk)]
                    hi = lo + int(np.count_nonzero(self._tuple_le(ks, bound)))
                if hi > lo:
                    take.append((ri, lo, hi))
                    heads[ri] = hi
            if not take:
                # every candidate sits above the bound (tie pathologies): the
                # bound-owning run's whole chunk is safe by construction
                ri, end = min(windows, key=lambda w: tuple(
                    runs[w[0]][f"k{i}"][w[1] - 1] for i in range(nk)))
                take = [(ri, heads[ri], end)]
                heads[ri] = end
            kparts = [np.concatenate([runs[ri][f"k{i}"][lo:hi]
                                      for ri, lo, hi in take])
                      for i in range(nk)]
            order = np.lexsort(tuple(reversed(kparts)))
            n = order.shape[0]
            out_cols: Dict[str, Column] = {}
            for name, typ, dict_ in self._run_schema:
                d = np.concatenate([runs[ri][f"d::{name}"][lo:hi]
                                    for ri, lo, hi in take])[order]
                vcat = None
                if any(f"v::{name}" in runs[ri] for ri, _, _ in take):
                    vcat = np.concatenate(
                        [runs[ri][f"v::{name}"][lo:hi]
                         if f"v::{name}" in runs[ri]
                         else np.ones(hi - lo, dtype=bool)
                         for ri, lo, hi in take])[order]
                out_cols[name] = Column(
                    jnp.asarray(d), None if vcat is None else jnp.asarray(vcat),
                    typ, dict_)
            pos = emitted + np.arange(n)
            live = pos >= self.offset
            if stop_at is not None:
                live = live & (pos < stop_at)
            emitted += n
            yield ColumnBatch(out_cols, jnp.asarray(live))


def _batch_bytes(b: ColumnBatch) -> int:
    total = 0
    for c in b.columns.values():
        total += c.data.nbytes + (c.valid.nbytes if c.valid is not None else 0)
    return total


class LimitOp(Operator):
    def __init__(self, child: Operator, limit: int, offset: int = 0):
        self.child = child
        self.limit = limit
        self.offset = offset

    def batches(self) -> Iterator[ColumnBatch]:
        remaining_skip = self.offset
        remaining = self.limit
        for b in self.child.batches():
            if remaining <= 0:
                break
            n = b.num_live()
            if n == 0:
                continue
            take_mask = K.limit_mask(b.live_mask(), remaining_skip, remaining)
            taken = min(max(n - remaining_skip, 0), remaining)
            remaining_skip = max(remaining_skip - n, 0)
            remaining -= taken
            yield ColumnBatch(b.columns, take_mask)


class DistinctOp(HashAggOp):
    def __init__(self, child: Operator, exprs: Sequence[Tuple[str, ir.Expr]],
                 max_groups: int = 1 << 16):
        super().__init__(child, exprs, [], max_groups)


def run_to_batch(op: Operator) -> ColumnBatch:
    """Drain an operator tree into a single compacted host batch."""
    return concat_batches(list(op.batches()))


class WindowOp(Operator):
    """Window functions: materialize, sort by (partition, order), scan-based frames.

    Output rows come back in window-sort order (SQL imposes no order without an outer
    ORDER BY); all payload columns are gathered through the same permutation."""

    def __init__(self, child: Operator, partitions, orders, calls,
                 out_schema=None):
        self.child = child
        self.partitions = list(partitions)   # [ir.Expr]
        self.orders = list(orders)           # [(ir.Expr, desc)]
        self.calls = list(calls)             # [L.WindowCall]
        # [(id, DataType, Dictionary)] — needed to shape EMPTY results
        self.out_schema = out_schema

    def _specs(self):
        inputs: List[ir.Expr] = []
        index: Dict[Tuple, int] = {}

        def arg_ix(e):
            k = expr_cache_key(e)
            if k not in index:
                index[k] = len(inputs)
                inputs.append(e)
            return index[k]

        lanes = []  # (lane_name, WindowSpec)
        for c in self.calls:
            frame = c.frame
            if c.kind in ("row_number", "rank", "dense_rank"):
                lanes.append((c.out_id, K.WindowSpec(c.kind, -1, 0, frame)))
            elif c.kind == "avg":
                ix = arg_ix(c.arg)
                lanes.append((c.out_id + "$sum", K.WindowSpec("sum", ix, 0, frame)))
                lanes.append((c.out_id + "$cnt", K.WindowSpec("count", ix, 0, frame)))
            else:
                lanes.append((c.out_id,
                              K.WindowSpec(c.kind, arg_ix(c.arg), c.offset, frame)))
        return inputs, lanes

    def batches(self) -> Iterator[ColumnBatch]:
        merged = concat_batches(list(self.child.batches()))
        if merged.capacity == 0:
            cols = dict(merged.columns)
            for fid, typ, dic in (self.out_schema or []):
                if fid not in cols:
                    cols[fid] = Column(np.zeros(0, dtype=typ.lane), None, typ, dic)
            yield ColumnBatch(cols, None)
            return
        padded = merged.pad_to(bucket_capacity(merged.capacity))
        inputs, lanes = self._specs()
        specs = tuple(s for _, s in lanes)
        key = ("window",
               tuple(expr_cache_key(p) for p in self.partitions),
               tuple((expr_cache_key(e), d) for e, d in self.orders),
               tuple(expr_cache_key(e) for e in inputs), specs)

        def build():
            comp = ExprCompiler(jnp)
            pfns = [comp.compile(p) for p in self.partitions]
            ofns = [(comp.compile(e), d) for e, d in self.orders]
            ifns = [comp.compile(e) for e in inputs]

            def run(batch: ColumnBatch):
                env = batch_env(batch)
                n = batch.capacity
                pk = [broadcast_value(n, *f(env)) for f in pfns]
                ok = []
                for f, desc in ofns:
                    d, v = broadcast_value(n, *f(env))
                    ok.append((d, v, desc, not desc))
                ins = [broadcast_value(n, *f(env)) for f in ifns]
                order, live_s, outs = K.window_eval(pk, ok, ins, specs,
                                                    batch.live_mask())
                cols = {}
                for name, c in batch.columns.items():
                    cols[name] = Column(c.data[order],
                                        c.valid[order] if c.valid is not None
                                        else None, c.dtype, c.dictionary)
                return cols, live_s, outs
            return jit_program(run)

        cols, live_s, outs = global_jit(key, build)(padded)
        yield self.finalize_calls(cols, live_s, outs, lanes)

    def finalize_calls(self, cols, live_s, outs, lanes) -> ColumnBatch:
        """Attach the window-call outputs to the permuted payload columns;
        avg = sum/count with MySQL decimal scale (shared with the MPP engine)."""
        cols = dict(cols)
        lane_map = {name: outs[i] for i, (name, _) in enumerate(lanes)}
        for c in self.calls:
            rt = c.dtype
            if c.kind == "avg":
                s, sv = lane_map[c.out_id + "$sum"]
                cnt, _ = lane_map[c.out_id + "$cnt"]
                s = np.asarray(s)
                cnt = np.asarray(cnt)
                safe = np.where(cnt == 0, 1, cnt)
                at = c.arg.dtype
                if rt.clazz == dt.TypeClass.DECIMAL:
                    shift = rt.scale - (at.scale if at.clazz == dt.TypeClass.DECIMAL
                                        else 0)
                    data = _signed_div_round(np, s.astype(np.int64)
                                             * _pow10(max(shift, 0)), safe)
                else:
                    data = (s.astype(np.float64) / safe).astype(np.float32)
                cols[c.out_id] = Column(jnp.asarray(data), jnp.asarray(cnt > 0),
                                        rt, None)
            else:
                d, v = lane_map[c.out_id]
                if c.kind == "sum" and rt.clazz == dt.TypeClass.FLOAT:
                    d = jnp.asarray(np.asarray(d, dtype=np.float32))
                dic = _find_dictionary(c.arg) if (c.arg is not None and
                                                  c.arg.dtype.is_string) else None
                cols[c.out_id] = Column(d, v, rt, dic)
        return ColumnBatch(cols, live_s)
