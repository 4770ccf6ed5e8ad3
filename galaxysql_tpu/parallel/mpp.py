"""MPP executor: the logical plan compiled to SPMD programs over a device mesh.

Reference analog: the whole MPP engine of SURVEY.md §2.7 — fragmenter, scheduler,
remote tasks, HTTP exchange — collapsed into its TPU-native shape (§7.1): a "stage" is
a shard_map program over the mesh; the exchange data plane is `all_to_all`/`all_gather`
over ICI (§5.8 plane-3 replacement); the scheduler is the host loop dispatching the
per-stage programs.  Tables are row-sharded (scan-split parallelism, §2.10); joins pick
broadcast vs hash-shuffle by estimated build size (the reference's
broadcast-vs-repartition `MppExchange` distribution choice).

Execution state is a DistBatch: column lanes either distributed 1-D [S*R] over the
mesh (shard s owns slice s) or replicated [N] on every device (post-merge results).  Unsupported plan shapes raise
NotSupportedError and the session falls back to the single-device engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from galaxysql_tpu.chunk.batch import (Column, ColumnBatch, Dictionary,
                                       dictionary_translation)
from galaxysql_tpu.exec.operators import (DISPATCH_STATS, AggCall, HashAggOp,
                                          SortOp, SourceOp, broadcast_value,
                                          bucket_capacity, expr_cache_key,
                                          global_jit, jit_program)
from galaxysql_tpu.exec import skew
from galaxysql_tpu.expr import ir
from galaxysql_tpu.expr.compiler import ExprCompiler, _find_dictionary
from galaxysql_tpu.kernels import relational as K
from galaxysql_tpu.runtime import exec_platform
from galaxysql_tpu.parallel import exchange
from galaxysql_tpu.parallel.mesh import GLOBAL_MESH_CACHE
from galaxysql_tpu.plan import logical as L
from galaxysql_tpu.plan.rules import estimate_rows
from galaxysql_tpu.utils import errors

BROADCAST_BUILD_LIMIT = 1 << 19  # est. rows: at or below, broadcast the build side

SHARD = P("shard")
REP = P()

# What the exchange plane moved, cumulative since process start, kept by the
# host loop at every dispatch of a program that exchanges: collective calls and
# the bytes ONE shard handed to each kind (`exchange.repartition_cost` /
# `broadcast_cost`, from static shapes; `(S - 1) / S` of them cross the
# interconnect), the slots those buffers held and the rows found live in them
# (both summed over the shards), overflow retries of the capacity ladders, and
# MPP statements, so that a per-statement value is a ratio of two of these.
# `compactions` counts the join sides `_compact` moved into fewer slots before
# their exchange, `compact_slots_in` / `compact_slots_out` the slots those
# sides had and kept (summed over the shards; a side that passed through as it
# was adds to none of the three).
EXCHANGE_STATS = {"statements": 0, "all_to_all_calls": 0, "all_to_all_bytes": 0,
                  "all_gather_calls": 0, "all_gather_bytes": 0,
                  "slots_offered": 0, "live_rows": 0, "overflow_retries": 0,
                  "compactions": 0, "compact_slots_in": 0,
                  "compact_slots_out": 0}
_COST_KEYS = ("all_to_all_calls", "all_to_all_bytes", "all_gather_calls",
              "all_gather_bytes", "slots")

# The equi-joins that ran to their end, cumulative since process start, by the
# plan's join kind and the exchange the executor gave it (`<kind>_<exchange>`,
# once a join whatever its ladder took), and `shuffle_build_rows`: the live
# build rows a shuffle or hybrid join's `all_to_all` delivered (over all
# shards, the rung that settled; a hybrid join's cold rows).  Read like EXCHANGE_STATS: a per-statement
# value is a ratio with the statements sent.
JOIN_KINDS = ("inner", "left", "semi", "anti")
JOIN_EXCHANGES = ("broadcast", "shuffle", "hybrid", "replicated")
MPP_JOIN_STATS = dict({f"{k}_{x}": 0 for k in JOIN_KINDS
                       for x in JOIN_EXCHANGES}, shuffle_build_rows=0)

# Where a capacity ladder settled: the key of its first rung's program (the
# plan's join, its sides' columns and slots, the sizes the formulas start
# from) -> the sizes that ran without an overflow.  The next statement with
# that key starts there, so a join whose pairs or whose co-located rows
# outgrow the formulas (an EXISTS over a fact table; a second shuffle on the
# key the first one dealt by) climbs once a process and not once a statement.
# Only a ladder that climbed is kept; sides of other sizes make another key.
_SETTLED: Dict[tuple, tuple] = {}
_SETTLED_LIMIT = 4096


def _ladder_settled(first_key: tuple, rung: tuple, retries: int):
    if not retries:
        return
    if len(_SETTLED) >= _SETTLED_LIMIT:
        _SETTLED.clear()
    _SETTLED[first_key] = rung


def _exchange_report(costs, *lives):
    """Inside a shard_map block, what a program returns beside its overflow
    flags, replicated: the static cost of its exchanges (`exchange.*_cost`
    dicts, summed into a constant vector) and the live rows of each mask on
    every shard, [S, len(lives)] int32 (one all_gather of a few integers) —
    fill and skew then cost the host no sync beyond the flags it reads
    anyway."""
    vec = jnp.asarray([sum(c.get(k, 0) for c in costs) for k in _COST_KEYS],
                      jnp.int64)
    counts = jnp.stack([jnp.sum(x, dtype=jnp.int32) for x in lives])
    return vec, jax.lax.all_gather(counts, "shard")


def _note_exchange(vec, exchanged, overflowed) -> Tuple[int, int]:
    """Add one dispatch's exchanges to EXCHANGE_STATS: `vec` is its
    `_exchange_report` cost vector, `exchanged` the [S, n] live counts of the
    buffers it exchanged (a gathered buffer reads the same on every shard and
    counts S times, as its slots do), `overflowed` whether the ladder goes
    round again.  Returns (live rows, slots), both over all shards."""
    for k, v in zip(_COST_KEYS[:4], vec):
        EXCHANGE_STATS[k] += int(v)
    live, slots = int(exchanged.sum()), int(vec[4]) * exchanged.shape[0]
    EXCHANGE_STATS["slots_offered"] += slots
    EXCHANGE_STATS["live_rows"] += live
    EXCHANGE_STATS["overflow_retries"] += bool(overflowed)
    return live, slots


def _gather_partials(r, costs):
    """Inside a shard_map block: every shard's partial groups on every shard
    (the merge stage's input), as (keys, aggs, live) of S x G slots."""
    pairs = list(r.keys) + list(r.aggs)
    lanes = _pack_lanes(pairs)
    costs.append(exchange.broadcast_cost(lanes, r.live.shape[0]))
    glanes, live_g = exchange.broadcast_all(lanes, r.live)
    moved = _unpack_lanes(glanes, pairs)
    return moved[:len(r.keys)], moved[len(r.keys):], live_g


def _fill(live_rows: int, slots: int) -> float:
    return round(live_rows / slots, 4) if slots else 0.0


def _shard_skew_ratio(per_shard) -> Optional[float]:
    """max/mean live rows per shard, or None for an empty stage."""
    total = float(np.sum(per_shard))
    if total <= 0:
        return None
    mean = total / len(per_shard)
    return round(float(np.max(per_shard)) / mean, 2)


def _pack_lanes(pairs):
    """Flatten [(data, valid)] lanes into one exchange payload: data lanes
    first, then the non-None valid lanes — `_unpack_lanes` mirrors the
    layout.  The ONE home for this convention (shuffles, broadcasts and the
    salted-agg repartition all move lanes through it)."""
    return [d for d, _v in pairs] + [v for _d, v in pairs if v is not None]


def _unpack_lanes(out_lanes, template):
    """Rebuild [(data, valid)] pairs from an exchange's output lanes, using
    `template` (the pre-exchange pairs) for validity presence."""
    vix = len(template)
    res = []
    for i, (_d, v) in enumerate(template):
        nv = None
        if v is not None:
            nv = out_lanes[vix]
            vix += 1
        res.append((out_lanes[i], nv))
    return res


@dataclasses.dataclass
class DistBatch:
    columns: Dict[str, Column]
    live: Any
    replicated: bool  # True: lanes [N] identical everywhere; False: [S*R] sharded
    # live rows per shard, where the producing program returned them (joins)
    shard_rows: Optional[np.ndarray] = None
    # what the producing stage writes on its span (exchange kind, quotas, fill)
    stage_attrs: Optional[Dict[str, Any]] = None
    # (slots in, slots out) a shard, where `_compact` made this batch
    compacted: Optional[Tuple[int, int]] = None

    def env(self):
        return {n: (c.data, c.valid) for n, c in self.columns.items()}


def _join_block(benv, blive, penv, plive, bk, pk, kind, residual_pred, cap,
                build_ids, probe_ids, pairs_fn=K.hash_join_pairs):
    """Per-shard equi-join: returns ((cols, live), overflow).

    For inner/left the output region is [cap] matched pairs; left joins append a
    [R_probe] region of null-extended unmatched probe rows (fixed total shape).
    `pairs_fn` is the pair-enumeration kernel — the default sorted/CSR probe,
    or `hash_join_probe_hybrid` when the caller unioned broadcast + shuffled
    partitions (skew-aware hybrid join)."""
    bkeys = [f(benv) for f in bk]
    pkeys = [f(penv) for f in pk]
    pairs = pairs_fn(bkeys, pkeys, blive, plive, cap)
    over = pairs.overflow

    # what follows the pairs is named for a device profile (`exec/programs.py`
    # STAGES): both sides' columns gathered at the pair slots and the
    # residual on them, then which probe rows kept a pair
    with jax.named_scope("join_block/gather"):
        bcols = {i: (benv[i][0][pairs.build_idx],
                     None if benv[i][1] is None
                     else benv[i][1][pairs.build_idx])
                 for i in build_ids}
        pcols = {i: (penv[i][0][pairs.probe_idx],
                     None if penv[i][1] is None
                     else penv[i][1][pairs.probe_idx])
                 for i in probe_ids}
        live = pairs.live
        if residual_pred is not None:
            live = live & residual_pred({**bcols, **pcols})

    if kind in ("semi", "anti"):
        with jax.named_scope("join_block/matched"):
            matched = K.probe_matched_from(live, pairs.probe_starts,
                                           pairs.probe_offsets)
            out_live = plive & (matched if kind == "semi" else ~matched)
        return ({i: penv[i] for i in probe_ids}, out_live), over

    if kind == "left":
        with jax.named_scope("join_block/matched"):
            matched = K.probe_matched_from(live, pairs.probe_starts,
                                           pairs.probe_offsets)
        unmatched = plive & ~matched
        out = {}
        for i in build_ids:
            d, v = bcols[i]
            nd = jnp.zeros(plive.shape[0], dtype=d.dtype)
            out[i] = (jnp.concatenate([d, nd]),
                      jnp.concatenate([v if v is not None else
                                       jnp.ones_like(live),
                                       jnp.zeros(plive.shape[0], jnp.bool_)]))
        for i in probe_ids:
            d, v = pcols[i]
            pd, pv = penv[i]
            out[i] = (jnp.concatenate([d, pd]),
                      None if (v is None and pv is None) else
                      jnp.concatenate([v if v is not None else jnp.ones_like(live),
                                       pv if pv is not None else
                                       jnp.ones_like(unmatched)]))
        out_live = jnp.concatenate([live, unmatched])
        return (out, out_live), over

    # inner
    return ({**bcols, **pcols}, live), over


class MppExecutor:
    def __init__(self, ctx, mesh: Mesh):
        self.ctx = ctx
        self.mesh = mesh
        self.S = mesh.shape["shard"]
        self._depth = 0      # nesting of traced `run` calls
        self._pending = []   # traced stages whose row counts are still on device

    # -- entry ---------------------------------------------------------------

    def execute(self, node: L.RelNode) -> ColumnBatch:
        EXCHANGE_STATS["statements"] += 1
        return self._to_host(self.run(node))

    def _to_host(self, b: DistBatch) -> ColumnBatch:
        cols = {name: Column(np.asarray(c.data),
                             None if c.valid is None else np.asarray(c.valid),
                             c.dtype, c.dictionary)
                for name, c in b.columns.items()}
        return ColumnBatch(cols, np.asarray(b.live)).compact()

    def _gather(self, b: DistBatch) -> DistBatch:
        """Distributed -> replicated (host-mediated; used for small results)."""
        host = self._to_host(b)
        n = host.capacity
        cols = {nm: Column(jnp.asarray(c.np_data()),
                           None if c.valid is None else jnp.asarray(c.np_valid()),
                           c.dtype, c.dictionary) for nm, c in host.columns.items()}
        return DistBatch(cols, jnp.ones(n, jnp.bool_) if n else
                         jnp.zeros(0, jnp.bool_), True)

    # -- dispatch ----------------------------------------------------------------

    def run(self, node: L.RelNode) -> DistBatch:
        from galaxysql_tpu.utils import tracing
        # MPP stage boundary: a deadline-killed query aborts between stages
        # with a typed error instead of dispatching the rest of the plan
        self.ctx.check_deadline()
        tc = tracing.current()
        collecting = getattr(self.ctx, "collect_stats", False)
        if tc is None:
            return self._run_collect(node) if collecting \
                else self._run_node(node)
        # traced: one `stage` span per plan node (nested — the stage tree IS
        # the span tree), with per-shard child spans on sharded outputs so the
        # Chrome-trace export shows one row per shard and mesh skew is
        # visible.
        sp = tc.begin(f"mpp:{type(node).__name__}", kind="stage")
        self._depth += 1
        try:
            out = self._run_collect(node) if collecting \
                else self._run_node(node)
        finally:
            self._depth -= 1
            tc.end(sp)
        if out.stage_attrs:
            sp.attrs.update(out.stage_attrs)
        sp.attrs["replicated"] = out.replicated
        info = getattr(self.ctx, "skew_stats", {}).get(id(node))
        if info is not None:
            # the hybrid/salted decision rides the stage span (HotKeys /
            # Salted in information_schema.query_spans and /trace/<id>)
            sp.attrs["skew_exec"] = skew.explain_line(info)
        # rows per shard: S integers counted on the device (or the ones the
        # join program returned), read in ONE transfer when the outermost
        # stage is done, so the traced statement syncs where the untraced does
        self._pending.append((sp, self._shard_rows(out), out.replicated))
        if self._depth == 0:
            self._resolve_stage_rows(tc)
        return out

    def _shard_rows(self, out: DistBatch):
        """Live rows of `out` per shard ([S], or [1] for a replicated or an
        unevenly laid out batch), never the lane itself on the host."""
        if out.shard_rows is not None:
            return out.shard_rows
        n = int(out.live.shape[0])
        parts = self.S if not out.replicated and n and n % self.S == 0 else 1
        key = ("mpp_filter", "shard_rows", parts, n)

        def build():
            return jit_program(lambda live: jnp.sum(
                live.reshape(parts, -1), axis=1, dtype=jnp.int32))
        return global_jit(key, build)(out.live)

    def _resolve_stage_rows(self, tc):
        pending, self._pending = self._pending, []
        counts = jax.device_get([c for _sp, c, _rep in pending])
        for (sp, _c, replicated), per_shard in zip(pending, counts):
            per_shard = np.asarray(per_shard).reshape(-1)
            sp.attrs["rows"] = int(per_shard.sum())
            if replicated or per_shard.size != self.S:
                continue
            for si, rn in enumerate(per_shard):
                tc.add(f"shard{si}", kind="shard", parent=sp.span_id,
                       start_us=sp.start_us, dur_us=sp.dur_us,
                       shard=si, rows=int(rn))
                if tc.annotate:
                    # one SPMD program ran all shards: the chips' own planes
                    # hold each shard's time, this marker its row count
                    with tc.annotation(f"shard:{si}", rows=int(rn),
                                       stage=sp.name):
                        pass
            ratio = _shard_skew_ratio(per_shard)
            if ratio is not None:
                # skew = max/mean live rows per shard: 1.0 is perfectly
                # balanced, ~S means one shard holds everything
                sp.attrs["skew"] = ratio
                self._note_shard_skew(ratio)

    def _run_collect(self, node: L.RelNode) -> DistBatch:
        # profiling: per-stage wall + row counts (the reference's MPP
        # QueryStats/StageStats/TaskStats, §5.1).  Counting live rows forces a
        # device sync per stage — exactly why the default path never enters
        # this branch.
        import time as _t
        t0 = _t.perf_counter()
        out = self._run_node(node)
        if any(st.get("node_id") == id(node) for st in self.ctx.op_stats):
            # _streaming_chain already reported this node (fused entry with
            # per-stage rows) — a second plain entry would double-count it
            return out
        per_shard = np.asarray(self._shard_rows(out)).reshape(-1)
        st = {"node_id": id(node), "operator": type(node).__name__,
              "engine": "mpp", "batches": 1, "rows_out": int(per_shard.sum()),
              "wall_ms": round((_t.perf_counter() - t0) * 1000, 3),
              "replicated": out.replicated}
        if not out.replicated and per_shard.size == self.S:
            # per-shard task stats: shard s owns slice s of the [S*R] layout
            st["rows_per_shard"] = [int(x) for x in per_shard]
            ratio = _shard_skew_ratio(per_shard)
            if ratio is not None:
                st["shard_skew"] = ratio
                self._note_shard_skew(ratio)
        self.ctx.op_stats.append(st)
        return out

    def _note_shard_skew(self, ratio: float):
        """`mpp_shard_skew` gauge: max/mean live rows per shard of the last
        profiled/traced MPP stage (slow-query triage without a full trace)."""
        inst = getattr(self.ctx, "archive_instance", None)
        m = getattr(inst, "metrics", None)
        if m is not None:
            m.gauge("mpp_shard_skew",
                    "max/mean live rows per shard (last profiled MPP stage)"
                    ).set(ratio)

    def _run_node(self, node: L.RelNode) -> DistBatch:
        if isinstance(node, L.Scan):
            return self._scan(node)
        if isinstance(node, L.Filter):
            if self._fusing():
                return self._streaming_chain(node)
            return self._filter(node)
        if isinstance(node, L.Project):
            if self._fusing():
                return self._streaming_chain(node)
            return self._project(node)
        if isinstance(node, L.Aggregate):
            return self._aggregate_cached(node)
        if isinstance(node, L.Join):
            return self._join(node)
        if isinstance(node, L.Sort):
            return self._sort(node)
        if isinstance(node, L.Limit):
            return self._limit(node)
        if isinstance(node, L.Window):
            return self._window(node)
        if isinstance(node, L.Union):
            return self._union(node)
        raise errors.NotSupportedError(f"MPP: {type(node).__name__}")

    # -- scan ---------------------------------------------------------------------

    def _scan(self, node: L.Scan) -> DistBatch:
        if node.as_of is not None:
            # flashback reads run on the local engine (loud fallback):
            # device-cached MPP lanes are keyed by current table version only
            raise errors.NotSupportedError("AS OF scan under MPP")
        if getattr(node.table, "remote", None) is not None:
            raise errors.NotSupportedError("remote-table scan under MPP")
        t = node.table
        key = f"{t.schema.lower()}.{t.name.lower()}"
        store = self.ctx.stores[key]
        storage_cols = [c for _, c in node.columns]
        st = GLOBAL_MESH_CACHE.get(store, self.mesh, storage_cols,
                                   self.ctx.snapshot_ts, self.ctx.txn_id)
        cols = {oid: st.columns[cname] for oid, cname in node.columns}
        self.ctx.trace.append(f"mpp-scan {t.name} shards={self.S}")
        hot = DistBatch(cols, st.live, False)
        am = getattr(self.ctx, "archive", None)
        if am is not None and am.files_for(key, self.ctx.snapshot_ts):
            hot = self._concat_shards([hot, self._archive_scan(node, am, key)])
        return self._apply_scan_rf(node, hot)

    def _apply_scan_rf(self, node: L.Scan, batch: DistBatch) -> DistBatch:
        """Planned runtime filters on an MPP probe-side scan: the build side's
        published filter (built once on the host by _join) masks the shard's
        live rows before any probe-stage dispatch.  The rf-only FusedSegment
        runs directly over the distributed lanes — the flags/range are
        replicated runtime args, same program shape as the local engine."""
        rf = getattr(self.ctx, "rf", None)
        seg = rf.segment_for_scan(node) if rf is not None else None
        if seg is None:
            return batch
        if seg.inert():
            return batch  # filters never published: skip the identity program
        sink = None
        if getattr(self.ctx, "collect_stats", False):
            sink = []
            seg.stats_sink = sink
        _out, live = seg.run_env(batch.env(), batch.live)
        self.ctx.trace.append(
            f"mpp-rf-scan {node.table.name} filters={len(seg.stages)}")
        if sink:
            from galaxysql_tpu.plan.physical import record_rf_stats
            record_rf_stats(self.ctx, seg, node,
                            np.sum([c for c, _ in sink], axis=0))
        return DistBatch(batch.columns, live, batch.replicated)

    def _archive_scan(self, node: L.Scan, am, key: str) -> DistBatch:
        """Cold parquet rows row-sharded over the mesh: host-read, padded to a
        multiple of S, laid out so shard s owns slice s (OSSTableScanExec analog;
        archive scans join the same MPP plan as hot data)."""
        from galaxysql_tpu.exec.operators import concat_batches
        inst = getattr(self.ctx, "archive_instance", None)
        t = node.table
        storage_cols = [c for _, c in node.columns]
        batches = list(am.scan_archive(inst, t.schema, t.name, storage_cols,
                                       self.ctx.snapshot_ts))
        merged = concat_batches(batches)
        n = merged.capacity
        Ra = max((n + self.S - 1) // self.S, 1)
        cols = {}
        for oid, cname in node.columns:
            c = merged.columns.get(cname) if n else None
            cm = t.column(cname)
            if c is None:
                data = np.zeros(self.S * Ra, dtype=cm.dtype.lane)
                valid = None
            else:
                data = np.zeros(self.S * Ra, dtype=np.asarray(c.np_data()).dtype)
                data[:n] = c.np_data()
                valid = None
                if c.valid is not None:
                    valid = np.zeros(self.S * Ra, dtype=np.bool_)
                    valid[:n] = c.np_valid()
            dic = t.dictionaries.get(cname.lower()) if cm.dtype.is_string else None
            cols[oid] = Column(jnp.asarray(data),
                               None if valid is None else jnp.asarray(valid),
                               cm.dtype, dic)
        live = np.zeros(self.S * Ra, dtype=np.bool_)
        live[:n] = True
        self.ctx.trace.append(f"mpp-scan-archive {t.name} rows={n}")
        return DistBatch(cols, jnp.asarray(live), False)

    # -- stateless row ops ---------------------------------------------------------

    def _fusing(self) -> bool:
        # direct read: every ExecContext defines it, and a context type that
        # forgot the field must fail loudly, not silently bypass NO_FUSE
        return self.ctx.enable_fusion

    def _streaming_chain(self, node) -> DistBatch:
        """Maximal Filter/Project chain as ONE fused program (exec/fusion.py).

        Elementwise stages need no shard_map of their own: the fused jit runs
        directly on the distributed lanes, exactly like the per-node _filter/
        _project programs it replaces — but paying one dispatch for the whole
        chain, and returning only computed lanes (passthrough column buffers
        are reattached, never copied through XLA outputs).  The compiled
        program is shared with the single-chip executor via global_jit."""
        from galaxysql_tpu.exec.fusion import chain_nodes, segment_for
        base, seg = segment_for(node, rf=getattr(self.ctx, "rf", None))
        sink = None
        if getattr(self.ctx, "collect_stats", False):
            sink = []
            seg.stats_sink = sink  # per-stage rows inside the fused chain
        child = self.run(base)
        if len(seg.stages) >= 2:
            self.ctx.trace.append(f"mpp-fuse-segment {seg.chain}")
        out, live = seg.run_env(child.env(), child.live)
        if sink:
            totals = np.sum([c for c, _ in sink], axis=0)
            wall = round(sum(w for _, w in sink), 3)
            from galaxysql_tpu.plan.physical import record_rf_stats
            record_rf_stats(self.ctx, seg,
                            base if isinstance(base, L.Scan) else None, totals)
            off = 1 + seg.rf_stage_count  # input count + rf prelude stages
            for i, nd in enumerate(chain_nodes(node)):
                self.ctx.op_stats.append(
                    {"node_id": id(nd), "operator": type(nd).__name__,
                     "engine": "mpp", "batches": len(sink),
                     "rows_out": int(totals[off + i]), "wall_ms": wall,
                     "fused": True, "segment": seg.chain})
        cols = seg.attach_columns(child.columns, out)
        return DistBatch(cols, live, child.replicated)

    def _filter(self, node: L.Filter) -> DistBatch:
        child = self.run(node.child)
        key = ("mpp_filter", expr_cache_key(node.cond))

        def build():
            pred = ExprCompiler(jnp).compile_predicate(node.cond)
            return jit_program(lambda env, live: live & pred(env))
        DISPATCH_STATS["dispatches"] += 1
        live = global_jit(key, build)(child.env(), child.live)
        return DistBatch(child.columns, live, child.replicated)

    def _project(self, node: L.Project) -> DistBatch:
        child = self.run(node.child)
        key = ("mpp_project", tuple((n, expr_cache_key(e)) for n, e in node.exprs))

        def build():
            comp = ExprCompiler(jnp)
            fns = [(name, comp.compile(e)) for name, e in node.exprs]

            def run(env, live):
                out = {}
                for name, f in fns:
                    d, v = f(env)
                    if d.shape != live.shape:
                        d = jnp.broadcast_to(d, live.shape)
                    if v is not None and v.shape != live.shape:
                        v = jnp.broadcast_to(v, live.shape)
                    out[name] = (d, v)
                return out
            return jit_program(run)
        DISPATCH_STATS["dispatches"] += 1
        out = global_jit(key, build)(child.env(), child.live)
        cols = {name: Column(out[name][0], out[name][1], e.dtype, _find_dictionary(e))
                for name, e in node.exprs}
        return DistBatch(cols, child.live, child.replicated)

    # -- aggregate -----------------------------------------------------------------

    def _aggregate_cached(self, node: L.Aggregate) -> DistBatch:
        """Fragment-cached aggregate: the grouped output is deterministic and
        version-keyed, so a warm repeated query replays it instead of
        re-running the whole SPMD stage tree.  Profiling runs bypass (the
        stats must describe the real stages)."""
        from galaxysql_tpu.exec import fragment_cache as fc
        cache = getattr(self.ctx, "frag", None)
        if cache is None or getattr(self.ctx, "collect_stats", False):
            return self._aggregate(node)
        fkey = fc.fingerprint(node, self.ctx)
        if fkey is None:
            return self._aggregate(node)
        akey = ("mpp_agg", fkey.key, self.S, id(self.mesh))
        got = cache.get(akey)
        if got is not None:
            self.ctx.trace.append(
                f"frag-cache mpp agg hit [{','.join(sorted(fkey.tables))}]")
            return got
        out = self._aggregate(node)
        cache.put(akey, out, fc._nbytes_of(out), fkey.tables,
                  kind="mpp_agg", rows=int(out.live.shape[0]))
        return out

    def _aggregate(self, node: L.Aggregate) -> DistBatch:
        calls = [AggCall(a.kind, a.arg, a.out_id) for a in node.aggs]
        child_node, prelude = node.child, None
        if self._fusing():
            # hand the feeding Filter/Project chain to the fuser: it compiles
            # INTO the per-shard partial-agg program (one dispatch per stage
            # round instead of one per operator), same as the local engine;
            # the base scan's runtime filters ride along as rf prelude stages
            from galaxysql_tpu.exec.fusion import segment_for
            base, prelude = segment_for(node.child,
                                        rf=getattr(self.ctx, "rf", None))
            if prelude is not None:
                child_node = base
                self.ctx.trace.append(f"mpp-fuse-agg-prelude {prelude.chain}")
        child = self.run(child_node)
        factor = skew.active_salt(node, self.ctx, self.S)
        if factor is not None and not child.replicated:
            p = node.salt_plan
            self.ctx.trace.append(
                f"mpp-salted-agg factor={factor} col={p.table}.{p.column}")
            skew.note(self.ctx, node, kind="agg", factor=factor,
                      column=f"{p.table}.{p.column}")
            return self._aggregate_salted(child, node.groups, calls,
                                          estimate_rows(node), factor,
                                          prelude=prelude)
        return self._aggregate_batch(child, node.groups, calls,
                                     estimate_rows(node), prelude=prelude)

    def _aggregate_batch(self, child: DistBatch, groups, calls,
                         est: float, prelude=None) -> DistBatch:
        helper = HashAggOp(None, groups, calls)  # spec decomposition + finalize
        inputs, lanes = helper._partial_specs()
        lane_names = tuple(name for name, _ in lanes)
        specs = tuple(s for _, s in lanes)
        merge_specs = tuple(
            K.AggSpec("sum" if s.kind in ("count", "count_star", "sum") else s.kind, i)
            for i, (_, s) in enumerate(lanes))

        G = 1 << max(int(est * 2).bit_length(), 8)
        while True:
            r, overflow = self._agg_round(groups, child, inputs, specs,
                                          merge_specs, G, prelude)
            if not overflow:
                break
            G *= 2
            if G > (1 << 22):
                raise errors.TddlError("MPP aggregation exceeds group ceiling")
        batch = helper._finalize(jax.tree.map(jnp.asarray, r), lane_names)
        return DistBatch(batch.columns, batch.live_mask(), True)

    def _agg_round(self, groups, child, inputs, specs, merge_specs, G,
                   prelude=None):
        key = ("mpp_agg", exec_platform(),
               tuple((n, expr_cache_key(e)) for n, e in groups),
               tuple(expr_cache_key(e) for e in inputs), specs, G,
               child.replicated, self.S,
               prelude.key() if prelude is not None else None)

        def build():
            papply = prelude.build_apply(jnp) if prelude is not None else None
            gfns, ifns = _agg_expr_fns(groups, inputs)

            def local_partial(env, live, plits):
                n = live.shape[0]
                if papply is not None:
                    env, live = papply(env, live, plits)
                keys = [broadcast_value(n, *f(env)) for f in gfns]
                ins = [broadcast_value(n, *f(env)) for f in ifns]
                return K.groupby(keys, ins, specs, live, G)

            if child.replicated:
                def run_rep(env, live, plits):
                    r = local_partial(env, live, plits)
                    return r, r.overflow
                return jit_program(run_rep)

            def spmd(env, live, plits):
                r = local_partial(env, live, plits)
                over = r.overflow
                costs = []
                flat_keys, flat_aggs, live_g = _gather_partials(r, costs)
                m = K.groupby(flat_keys, flat_aggs, merge_specs, live_g, G)
                over = jax.lax.pmax((over | m.overflow).astype(jnp.int32),
                                    "shard").astype(jnp.bool_)
                return m, (over, _exchange_report(costs, live_g))

            fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD, SHARD, REP),
                           out_specs=(REP, REP), check_vma=False)
            return jit_program(fn)

        plits = prelude.lits() if prelude is not None else ()
        DISPATCH_STATS["dispatches"] += 1
        r, res = global_jit(key, build)(child.env(), child.live, plits)
        if child.replicated:
            return r, bool(res)
        overflow, (vec, counts) = jax.device_get(res)
        _note_exchange(vec, counts, overflow)
        return r, bool(overflow)

    def _aggregate_salted(self, child: DistBatch, groups, calls, est: float,
                          factor: int, prelude=None) -> DistBatch:
        """Skew-aware salted aggregation (plan/rules.plan_skew's SaltAggPlan).

        Rows repartition on hash(group key, salt) with salt = row % factor —
        a hot group's rows spread over `factor` destination shards instead of
        piling one — then each shard aggregates its received rows and a final
        merge stage re-combines the (at most factor x S) partials per group.
        One fused SPMD program per round, same overflow-retry discipline and
        finalize as the default partial-merge path, so results are identical
        up to float-summation order."""
        helper = HashAggOp(None, groups, calls)
        inputs, lanes = helper._partial_specs()
        lane_names = tuple(name for name, _ in lanes)
        specs = tuple(s for _, s in lanes)
        merge_specs = tuple(
            K.AggSpec("sum" if s.kind in ("count", "count_star", "sum")
                      else s.kind, i)
            for i, (_, s) in enumerate(lanes))
        R = int(child.live.shape[0]) // self.S
        quota = max(2 * R // self.S, 128)
        G = 1 << max(int(est * 2).bit_length(), 8)
        while True:
            r, over_shuffle, over_groups = self._salted_agg_round(
                groups, child, inputs, specs, merge_specs, G, factor, quota,
                prelude)
            if not (over_shuffle or over_groups):
                break
            if over_shuffle:
                quota *= 2
            if over_groups:
                G *= 2
            if max(quota, G) > (1 << 22):
                raise errors.TddlError(
                    "MPP salted aggregation exceeds capacity ceiling")
        batch = helper._finalize(jax.tree.map(jnp.asarray, r), lane_names)
        return DistBatch(batch.columns, batch.live_mask(), True)

    def _salted_agg_round(self, groups, child, inputs, specs, merge_specs,
                          G, factor, quota, prelude=None):
        key = ("mpp_agg_salt", exec_platform(),
               tuple((n, expr_cache_key(e)) for n, e in groups),
               tuple(expr_cache_key(e) for e in inputs), specs, G, factor,
               self.S, quota,
               prelude.key() if prelude is not None else None)

        def build():
            papply = prelude.build_apply(jnp) if prelude is not None else None
            gfns, ifns = _agg_expr_fns(groups, inputs)

            def spmd(env, live, plits):
                if papply is not None:
                    env, live = papply(env, live, plits)
                n = live.shape[0]
                keys0 = [broadcast_value(n, *f(env)) for f in gfns]
                ins0 = [broadcast_value(n, *f(env)) for f in ifns]
                # salted destination: the key hash (NULL-tagged, exactly the
                # lane a plain repartition would use) mixed with row % factor
                kh = K.hash_columns(keys0) if keys0 else \
                    jnp.zeros(n, jnp.uint64)
                salt = jnp.arange(n, dtype=jnp.uint64) % jnp.uint64(factor)
                dh = K.hash_columns([(kh, None), (salt, None)])
                pairs = keys0 + ins0
                lanes = _pack_lanes(pairs)
                costs = [exchange.repartition_cost(lanes, quota)]
                out_lanes, live_x, over_x = exchange.repartition_by_hash(
                    lanes, live, dh, quota)
                moved = _unpack_lanes(out_lanes, pairs)
                keys = moved[:len(keys0)]
                ins = moved[len(keys0):]
                r = K.groupby(keys, ins, specs, live_x, G)

                # final merge stage: gather every shard's partial groups and
                # re-combine the salt buckets (replicated result)
                flat_keys, flat_aggs, live_g = _gather_partials(r, costs)
                m = K.groupby(flat_keys, flat_aggs, merge_specs, live_g, G)

                def rep(x):
                    return jax.lax.pmax(x.astype(jnp.int32),
                                        "shard").astype(jnp.bool_)
                return m, ((rep(over_x), rep(r.overflow | m.overflow)),
                           _exchange_report(costs, live_x, live_g))

            fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD, SHARD, REP),
                           out_specs=(REP, REP), check_vma=False)
            return jit_program(fn)

        plits = prelude.lits() if prelude is not None else ()
        DISPATCH_STATS["dispatches"] += 1
        r, res = global_jit(key, build)(child.env(), child.live, plits)
        flags, (vec, counts) = jax.device_get(res)
        over_shuffle, over_groups = (bool(x) for x in flags)
        _note_exchange(vec, counts, over_shuffle or over_groups)
        return r, over_shuffle, over_groups

    # -- join ------------------------------------------------------------------------

    def _join(self, node: L.Join) -> DistBatch:
        if node.kind == "cross":
            left = self.run(node.left)
            right = self.run(node.right)
            # cross product is symmetric: keep a distributed side as the "left"
            # (stays sharded), replicate the other (small: scalar subqueries,
            # aggregated views — the reference's NestedLoopJoinExec analog)
            if left.replicated and not right.replicated:
                left, right = right, left
            if not right.replicated:
                right = self._gather(right)
            if int(np.asarray(right.live).sum()) == 1:
                return self._cross_attach(left, right)
            return self._cross_product(left, right)

        # build = right side by default; inner joins may flip to the smaller side
        build_node, probe_node = node.right, node.left
        build_keys = [b for _, b in node.equi]
        probe_keys = [a for a, _ in node.equi]
        if node.kind == "inner" and \
                estimate_rows(node.left) < estimate_rows(node.right) / 4:
            build_node, probe_node = node.left, node.right
            build_keys, probe_keys = probe_keys, build_keys

        # the exchange is chosen on the ESTIMATE, before either side's rows
        # are counted: observed counts shape the programs, not the plan
        broadcast = estimate_rows(build_node) <= BROADCAST_BUILD_LIMIT
        build = self._build_side(node, build_node, broadcast)
        broadcast = broadcast or build.replicated
        probe = self.run(probe_node)
        if probe.replicated:
            probe = build_replicated_to_dist_error(node)
        probe = self._compact(probe, broadcast)
        build_ids = list(build.columns.keys())
        probe_ids = list(probe.columns.keys())

        if broadcast:
            out = self._broadcast_join(node, build, probe, build_keys, probe_keys,
                                       build_ids, probe_ids)
        else:
            # shuffle shape: a heavy-hitter probe key would pile one shard —
            # hybrid-split when planning planted a skew plan for the side we
            # actually probe AND its stats survive the runtime re-check
            active = skew.active_join_skew(
                node, self.ctx, "left" if probe_node is node.left else "right",
                self.S)
            if active is not None:
                out = self._hybrid_join(node, build, probe, build_keys,
                                        probe_keys, build_ids, probe_ids,
                                        active)
            else:
                out = self._shuffle_join(node, build, probe, build_keys,
                                         probe_keys, build_ids, probe_ids)
        return self._join_result(node, out, build, probe)

    def _compact(self, batch: DistBatch, broadcast: bool) -> DistBatch:
        """A join side before it is exchanged or probed, every shard's live
        rows moved (in their order) into `R' = bucket_capacity(most live rows
        on a shard)` slots, so that the join's quotas, its `cap` and what it
        hands on are sized from rows and not from the slots a filter or an
        earlier join left empty.  The counts are the producing join's own, or
        S integers counted on the device and read here: one small transfer a
        side, on a path that reads its overflow flags after every join.

        Engages where R' is at most half the side's slots a shard; a denser
        side (a base table) is returned as it is.  R' holds the fullest
        shard, so nothing overflows and no ladder is needed.  The program
        takes the name of the join it feeds (`broadcast`: the exchange the
        caller chose; a hybrid join is a shuffle)."""
        n = int(batch.live.shape[0])
        if batch.replicated or not n or n % self.S:
            return batch
        counts = np.asarray(self._shard_rows(batch)).reshape(-1)
        R, Rc = n // self.S, bucket_capacity(int(counts.max()))
        if 2 * Rc > R:
            return batch
        ids = list(batch.columns.keys())
        if broadcast:
            key = ("mpp_bjoin", "compact", tuple(ids), self.S, R, Rc)
        else:
            key = ("mpp_sjoin", "compact", tuple(ids), self.S, R, Rc)

        def builder():
            def spmd(env, live):
                pairs = [env[i] for i in ids]
                lanes, keep = exchange.compact_rows(_pack_lanes(pairs), live,
                                                    Rc)
                return dict(zip(ids, _unpack_lanes(lanes, pairs))), keep

            fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD, SHARD),
                           out_specs=(SHARD, SHARD), check_vma=False)
            return jit_program(fn)

        DISPATCH_STATS["dispatches"] += 1
        cols_o, live = global_jit(key, builder)(batch.env(), batch.live)
        EXCHANGE_STATS["compactions"] += 1
        EXCHANGE_STATS["compact_slots_in"] += n
        EXCHANGE_STATS["compact_slots_out"] += self.S * Rc
        cols = {i: Column(cols_o[i][0], cols_o[i][1], c.dtype, c.dictionary)
                for i, c in batch.columns.items()}
        return DistBatch(cols, live, False, shard_rows=counts,
                         compacted=(R, Rc))

    def _build_side(self, node: L.Join, build_node: L.RelNode,
                    broadcast: bool) -> DistBatch:
        """Run (or reuse) a join's build side, compacted (`_compact`) as soon
        as it exists, so that the runtime filter's publication reads `S x R'`
        key slots back.  The distributed build lanes + the runtime filters
        published from them are fragment-cached per mesh: a warm join goes
        straight to the probe subtree with the sharded build already
        device-resident and the filters already in hand."""
        from galaxysql_tpu.exec import fragment_cache as fc
        from galaxysql_tpu.exec import runtime_filter as rfmod
        build_is_left = build_node is node.left
        cache = getattr(self.ctx, "frag", None)
        akey = None
        active_specs = rfmod.specs_for(
            node, "right" if build_is_left else "left",
            getattr(self.ctx, "rf", None))
        if cache is not None:
            fkey = fc.fingerprint(build_node, self.ctx)
            if fkey is not None:
                # the active filter-spec set is part of the identity: a
                # RUNTIME_FILTER(OFF) run must not poison the filters-on path
                rf_sig = tuple(sorted((s.filter_id, tuple(sorted(s.kinds)))
                                      for s in active_specs))
                akey = ("mpp_build", fkey.key, self.S, id(self.mesh), rf_sig)
                art = cache.get(akey)
                if art is not None:
                    self.ctx.trace.append(
                        f"frag-cache mpp build hit "
                        f"[{','.join(sorted(fkey.tables))}]")
                    if getattr(self.ctx, "collect_stats", False):
                        self.ctx.op_stats.append(
                            {"node_id": id(build_node), "engine": "mpp",
                             "operator": type(build_node).__name__,
                             "batches": 0, "rows_out": art.rows,
                             "wall_ms": 0.0, "cached": True})
                    rfmod.publish_captured(getattr(self.ctx, "rf", None),
                                           active_specs, art.filters)
                    return art.batch
        build = self._compact(self.run(build_node), broadcast)
        specs = self._publish_rf(node, build, build_is_left)
        if akey is not None:
            art = fc.BuildArtifact(batch=build)
            art.rows = int(build.live.shape[0])
            art.filters = rfmod.capture_published(
                getattr(self.ctx, "rf", None), specs)
            cache.put(akey, art, fc.artifact_nbytes(art), fkey.tables,
                      kind="mpp_build", rows=art.rows)
        return build

    def _publish_rf(self, node: L.Join, build: DistBatch, build_is_left: bool):
        from galaxysql_tpu.exec import runtime_filter as rfmod
        rf = getattr(self.ctx, "rf", None)
        probe_side = "right" if build_is_left else "left"
        specs = rfmod.specs_for(node, probe_side, rf)
        if not specs:
            return []
        rfmod.publish_from_dist(rf, specs, build.columns, build.live)
        self.ctx.trace.append(f"mpp-rf-publish filters={len(specs)}")
        return specs

    @staticmethod
    def _join_key(node, build_keys, probe_keys, build_ids, probe_ids):
        """What a join program's key says of the plan's join: its kind, key
        expressions, residual and the two sides' columns."""
        return (node.kind,
                tuple(expr_cache_key(e) for e in build_keys),
                tuple(expr_cache_key(e) for e in probe_keys),
                expr_cache_key(node.residual) if node.residual is not None else None,
                tuple(build_ids), tuple(probe_ids))

    def _join_key_fns(self, build_keys, probe_keys):
        comp = ExprCompiler(jnp)
        bk, pk = [], []
        for be, pe in zip(build_keys, probe_keys):
            bf, pf = comp.compile(be), comp.compile(pe)
            if be.dtype.is_string and pe.dtype.is_string:
                db, dp = _find_dictionary(be), _find_dictionary(pe)
                if db is not None and dp is not None and db is not dp:
                    trans = dictionary_translation(db, dp)

                    def translated(env, _pf=pf, _t=trans):
                        d, v = _pf(env)
                        return jnp.asarray(_t)[d], v
                    pf = translated
            bk.append(bf)
            pk.append(pf)
        return bk, pk

    def _broadcast_join(self, node, build, probe, build_keys, probe_keys,
                        build_ids, probe_ids):
        probe_R = int(probe.live.shape[0]) // self.S
        cap = bucket_capacity(max(probe_R * 2, 1024))

        join = self._join_key(node, build_keys, probe_keys, build_ids,
                              probe_ids)
        first = ("mpp_bjoin", *join, build.replicated, self.S, cap,
                 int(build.live.shape[0]), probe_R)
        cap, = _SETTLED.get(first, (cap,))
        retries = 0
        while True:
            key = ("mpp_bjoin", *join, build.replicated, self.S, cap)

            def builder():
                bk, pk = self._join_key_fns(build_keys, probe_keys)
                residual_pred = (ExprCompiler(jnp).compile_predicate(node.residual)
                                 if node.residual is not None else None)
                build_rep = build.replicated
                kind = node.kind
                bids, pids = list(build_ids), list(probe_ids)
                _cap = cap

                def spmd(benv, blive, penv, plive):
                    costs = []
                    if not build_rep:
                        ids = list(benv.keys())
                        pairs = [benv[i] for i in ids]
                        lanes = _pack_lanes(pairs)
                        costs.append(exchange.broadcast_cost(lanes, blive.shape[0]))
                        glanes, blive = exchange.broadcast_all(lanes, blive)
                        benv = dict(zip(ids, _unpack_lanes(glanes, pairs)))
                    (cols, live), over = _join_block(
                        benv, blive, penv, plive, bk, pk, kind, residual_pred,
                        _cap, bids, pids)
                    over = jax.lax.pmax(over.astype(jnp.int32),
                                        "shard").astype(jnp.bool_)
                    # a gathered build side is whole on every shard
                    return (cols, live), (over, _exchange_report(
                        costs, live, *([blive] if costs else [])))

                in_specs = (REP if build_rep else SHARD,
                            REP if build_rep else SHARD, SHARD, SHARD)
                fn = shard_map(spmd, mesh=self.mesh, in_specs=in_specs,
                               out_specs=(SHARD, REP), check_vma=False)
                return jit_program(fn)

            out, res = global_jit(key, builder)(build.env(), build.live,
                                                probe.env(), probe.live)
            over, (vec, counts) = jax.device_get(res)
            live, slots = _note_exchange(vec, counts[:, 1:], over)
            if not bool(over):
                _ladder_settled(first, (cap,), retries)
                attrs = {"exchange": "replicated"} if build.replicated else {
                    "exchange": "broadcast", "build_slots": slots // self.S,
                    "build_rows": live // self.S, "fill": _fill(live, slots)}
                return out, counts[:, 0], dict(attrs, cap=cap, retries=retries)
            retries += 1
            cap *= 2
            if cap > (1 << 24):
                raise errors.TddlError("MPP join output exceeds capacity ceiling")

    def _shuffle_quotas(self, bR: int, pR: int) -> Tuple[int, int]:
        """Where the shuffle's quota ladders start: slots each (source,
        destination) pair carries, twice a uniform hash's share of the
        side's slots per shard.  A sparse side arrives compacted
        (`_compact`): its slots are the bucket above its fullest shard's
        rows.  A denser one arrives as it was produced, in under twice
        that."""
        return max(2 * bR // self.S, 128), max(2 * pR // self.S, 128)

    def _shuffle_join(self, node, build, probe, build_keys, probe_keys,
                      build_ids, probe_ids):
        quota_b, quota_p = self._shuffle_quotas(
            int(build.live.shape[0]) // self.S,
            int(probe.live.shape[0]) // self.S)
        cap = bucket_capacity(max(2 * quota_p * self.S, 1024))

        join = self._join_key(node, build_keys, probe_keys, build_ids,
                              probe_ids)
        first = ("mpp_sjoin", *join, self.S, quota_b, quota_p, cap)
        quota_b, quota_p, cap = _SETTLED.get(first, (quota_b, quota_p, cap))
        retries = 0
        while True:
            key = ("mpp_sjoin", *join, self.S, quota_b, quota_p, cap)

            def builder():
                bk, pk = self._join_key_fns(build_keys, probe_keys)
                residual_pred = (ExprCompiler(jnp).compile_predicate(node.residual)
                                 if node.residual is not None else None)
                kind = node.kind
                bids, pids = list(build_ids), list(probe_ids)
                _qb, _qp, _cap = quota_b, quota_p, cap

                def spmd(benv, blive, penv, plive):
                    costs = []

                    def shuffle_side(env, live, key_fns, quota):
                        keys = [f(env) for f in key_fns]
                        h = K.hash_columns(keys)
                        ids = list(env.keys())
                        pairs = [env[i] for i in ids]
                        lanes = _pack_lanes(pairs)
                        costs.append(exchange.repartition_cost(lanes, quota))
                        out_lanes, live_x, over = exchange.repartition_by_hash(
                            lanes, live, h, quota)
                        return (dict(zip(ids, _unpack_lanes(out_lanes,
                                                            pairs))),
                                live_x, over)

                    benv2, blive2, over_b = shuffle_side(benv, blive, bk, _qb)
                    penv2, plive2, over_p = shuffle_side(penv, plive, pk, _qp)
                    (cols, live), over_cap = _join_block(
                        benv2, blive2, penv2, plive2, bk, pk, kind, residual_pred,
                        _cap, bids, pids)

                    def rep(x):
                        return jax.lax.pmax(x.astype(jnp.int32),
                                            "shard").astype(jnp.bool_)
                    return (cols, live), (
                        (rep(over_b), rep(over_p), rep(over_cap)),
                        _exchange_report(costs, blive2, plive2, live))

                fn = shard_map(spmd, mesh=self.mesh,
                               in_specs=(SHARD, SHARD, SHARD, SHARD),
                               out_specs=(SHARD, REP), check_vma=False)
                return jit_program(fn)

            out, res = global_jit(key, builder)(build.env(), build.live,
                                                probe.env(), probe.live)
            flags, (vec, counts) = jax.device_get(res)
            over_b, over_p, over_cap = (bool(x) for x in flags)
            moved, slots = _note_exchange(vec, counts[:, :2],
                                          over_b or over_p or over_cap)
            if not (over_b or over_p or over_cap):
                _ladder_settled(first, (quota_b, quota_p, cap), retries)
                return out, counts[:, 2], {
                    "exchange": "shuffle", "quota_b": quota_b,
                    "quota_p": quota_p, "cap": cap, "retries": retries,
                    "build_rows": int(counts[:, 0].sum()),
                    "probe_rows": int(counts[:, 1].sum()),
                    "probe_skew": _shard_skew_ratio(counts[:, 1]),
                    "fill": _fill(moved, slots)}
            retries += 1
            if over_b:
                quota_b *= 2
            if over_p:
                quota_p *= 2
            if over_cap:
                cap *= 2
            if max(quota_b, quota_p, cap) > (1 << 24):
                raise errors.TddlError("MPP shuffle exceeds capacity ceiling")

    def _hybrid_join(self, node, build, probe, build_keys, probe_keys,
                     build_ids, probe_ids, active):
        """Skew-aware hybrid shuffle join (JSPIM-style hot/cold split).

        The skewed side's hot rows STAY WHERE THE SCAN LAYOUT ALREADY
        BALANCED THEM — the hash shuffle is what concentrates them — and the
        OTHER side's hot rows (few: the matching dimension/probe rows) are
        BROADCAST to every shard, compacted into a fixed `hot_quota` lane
        then all-gathered.  Cold rows of both sides hash-shuffle exactly as
        `_shuffle_join`, with quotas sized for the unskewed remainder.
        Orientation 'probe' = skew on the probe side (hot build rows
        broadcast); orientation 'build' = skew on the build side (hot probe
        rows broadcast; inner joins only — a broadcast probe row would
        multiply unmatched left/semi/anti semantics S-fold).  Each shard then
        probes the UNION of the broadcast and shuffled partitions through one
        `hash_join_probe_hybrid` pass, all fused under one global_jit key:
        the hot-hash set rides as a padded runtime argument, so steady-state
        retraces stay 0 while the hot keys drift.

        Classification is by the SAME combined key hash both repartitions
        use, computed on BOTH sides, so a hot row's matches are always
        resident (broadcast or local) and a cold row's matches always
        shuffle to its hash shard — each output pair materializes exactly
        once regardless of the hot set's contents."""
        hot = active.hot_hashes()
        H = max(8, 1 << max(len(hot) - 1, 0).bit_length())  # static pad ladder
        hot_h = np.zeros(H, np.uint64)
        hot_h[:len(hot)] = hot
        hot_v = np.zeros(H, np.bool_)
        hot_v[:len(hot)] = True
        skew_on_probe = active.orientation == "probe"
        bR = int(build.live.shape[0]) // self.S
        pR = int(probe.live.shape[0]) // self.S
        # the broadcast side carries few rows per hot key (dimension-style),
        # so start small and let the ladder grow; the kept-local hot rows of
        # the SKEWED side compact into their own quota lane (they are evenly
        # spread by scan layout, ~hot-mass x R per shard)
        hot_quota = max(2 * H, 128)
        loc_quota = max((pR if skew_on_probe else bR) // 2, 128)
        # the skewed side's cold shuffle excludes the hot mass — size its
        # quota for the remainder (the ladder covers sketch underestimates)
        cold = 1.0 - active.hot_mass()
        quota_b, quota_p = self._shuffle_quotas(bR, pR)
        if skew_on_probe:
            quota_p = max(int(quota_p * cold), 128)
        else:
            quota_b = max(int(quota_b * cold), 128)
        p = active.plan
        self.ctx.trace.append(
            f"mpp-hybrid-join hot={len(hot)} col={p.table}.{p.column} "
            f"skew={active.orientation}")
        skew.note(self.ctx, node, kind="join", hot=len(hot),
                  column=f"{p.table}.{p.column}")
        # pair capacity: the same sizing as _shuffle_join — hybrid pairs are
        # BALANCED across shards (that is the point), so the fair-share bound
        # holds where the plain shuffle's hot shard overflows it
        cap = bucket_capacity(max(2 * quota_p * self.S, 1024))

        kind, *join = self._join_key(node, build_keys, probe_keys, build_ids,
                                     probe_ids)
        first = ("mpp_hybrid_join", kind, active.orientation, *join, self.S,
                 H, hot_quota, loc_quota, quota_b, quota_p, cap, bR, pR)
        hot_quota, loc_quota, quota_b, quota_p, cap = _SETTLED.get(
            first, (hot_quota, loc_quota, quota_b, quota_p, cap))
        retries = 0
        while True:
            key = ("mpp_hybrid_join", kind, active.orientation, *join, self.S,
                   H, hot_quota, loc_quota, quota_b, quota_p, cap)

            def builder():
                bk, pk = self._join_key_fns(build_keys, probe_keys)
                residual_pred = (
                    ExprCompiler(jnp).compile_predicate(node.residual)
                    if node.residual is not None else None)
                kind = node.kind
                bids, pids = list(build_ids), list(probe_ids)
                _hq, _lq = hot_quota, loc_quota
                _qb, _qp, _cap = quota_b, quota_p, cap

                def shuffle_cold(env, live, h, quota, ids, costs):
                    pairs = [env[i] for i in ids]
                    lanes = _pack_lanes(pairs)
                    costs.append(exchange.repartition_cost(lanes, quota))
                    out_lanes, live_x, over = exchange.repartition_by_hash(
                        lanes, live, h, quota)
                    return (dict(zip(ids, _unpack_lanes(out_lanes, pairs))),
                            live_x, over)

                def compact_hot(env, hot_mask, ids, q):
                    """The rows under `hot_mask` at the front of a [q] lane
                    env (`exchange.compact_rows`, on every platform), and
                    whether there were more than `q` of them: the first `q`
                    are kept."""
                    pairs = [env[i] for i in ids]
                    lanes, clive = exchange.compact_rows(
                        _pack_lanes(pairs), hot_mask, q)
                    over = jnp.sum(hot_mask, dtype=jnp.int32) > q
                    return dict(zip(ids, _unpack_lanes(lanes, pairs))), clive, over

                def broadcast_hot(env, hot_mask, ids, costs):
                    # compact hot rows to _hq slots, then replicate
                    cenv, clive, over = compact_hot(env, hot_mask, ids, _hq)
                    pairs = [cenv[i] for i in ids]
                    lanes = _pack_lanes(pairs)
                    costs.append(exchange.broadcast_cost(lanes, _hq))
                    gl, glive = exchange.broadcast_all(lanes, clive)
                    return (dict(zip(ids, _unpack_lanes(gl, pairs))),
                            glive, over)

                def union(a_env, a_live, b_env, b_live, ids):
                    out = {}
                    for i in ids:
                        da, va = a_env[i]
                        db, vb = b_env[i]
                        d = jnp.concatenate([da, db])
                        v = None if (va is None and vb is None) else \
                            jnp.concatenate(
                                [va if va is not None else
                                 jnp.ones(da.shape[0], jnp.bool_),
                                 vb if vb is not None else
                                 jnp.ones(db.shape[0], jnp.bool_)])
                        out[i] = (d, v)
                    return out, jnp.concatenate([a_live, b_live])

                def spmd(benv, blive, penv, plive, hoth, hotv):
                    costs = []
                    bkeys_l = [f(benv) for f in bk]
                    pkeys_l = [f(penv) for f in pk]
                    hot_b = K.hot_key_mask(bkeys_l, hoth, hotv) & blive
                    hot_p = K.hot_key_mask(pkeys_l, hoth, hotv) & plive
                    bh = K.hash_columns(bkeys_l)
                    ph = K.hash_columns(pkeys_l)

                    # cold rows of both sides hash-shuffle as today
                    cb_env, cb_live, over_b = shuffle_cold(
                        benv, blive & ~hot_b, bh, _qb, bids, costs)
                    cp_env, cp_live, over_p = shuffle_cold(
                        penv, plive & ~hot_p, ph, _qp, pids, costs)

                    if skew_on_probe:
                        # hot build rows broadcast; hot probe rows stay
                        # local (compacted — their shard does not change)
                        ghot, ghot_live, over_h = broadcast_hot(
                            benv, hot_b, bids, costs)
                        lenv, llive, over_l = compact_hot(
                            penv, hot_p, pids, _lq)
                        ubenv, ublive = union(ghot, ghot_live,
                                              cb_env, cb_live, bids)
                        upenv, uplive = union(lenv, llive,
                                              cp_env, cp_live, pids)
                    else:
                        # skewed build: hot probe rows broadcast, hot build
                        # rows stay where the scan layout balanced them
                        ghot, ghot_live, over_h = broadcast_hot(
                            penv, hot_p, pids, costs)
                        lenv, llive, over_l = compact_hot(
                            benv, hot_b, bids, _lq)
                        ubenv, ublive = union(lenv, llive,
                                              cb_env, cb_live, bids)
                        upenv, uplive = union(ghot, ghot_live,
                                              cp_env, cp_live, pids)

                    (cols, live), over_cap = _join_block(
                        ubenv, ublive, upenv, uplive, bk, pk, kind,
                        residual_pred, _cap, bids, pids,
                        pairs_fn=K.hash_join_probe_hybrid)

                    def rep(x):
                        return jax.lax.pmax(x.astype(jnp.int32),
                                            "shard").astype(jnp.bool_)
                    return (cols, live), (
                        (rep(over_h), rep(over_l), rep(over_b), rep(over_p),
                         rep(over_cap)),
                        _exchange_report(costs, cb_live, cp_live, ghot_live,
                                         live))

                fn = shard_map(spmd, mesh=self.mesh,
                               in_specs=(SHARD, SHARD, SHARD, SHARD, REP, REP),
                               out_specs=(SHARD, REP), check_vma=False)
                return jit_program(fn)

            out, res = global_jit(key, builder)(
                build.env(), build.live, probe.env(), probe.live,
                jnp.asarray(hot_h), jnp.asarray(hot_v))
            flags, (vec, counts) = jax.device_get(res)
            over_h, over_l, over_b, over_p, over_cap = \
                (bool(x) for x in flags)
            overflowed = over_h or over_l or over_b or over_p or over_cap
            moved, slots = _note_exchange(vec, counts[:, :3], overflowed)
            if not overflowed:
                _ladder_settled(
                    first, (hot_quota, loc_quota, quota_b, quota_p, cap),
                    retries)
                return out, counts[:, 3], {
                    "exchange": "hybrid", "quota_b": quota_b,
                    "quota_p": quota_p, "hot_quota": hot_quota, "cap": cap,
                    "retries": retries, "fill": _fill(moved, slots),
                    "build_rows": int(counts[:, 0].sum())}
            retries += 1
            if over_h:
                hot_quota *= 2
            if over_l:
                loc_quota *= 2
            if over_b:
                quota_b *= 2
            if over_p:
                quota_p *= 2
            if over_cap:
                cap *= 2
            if max(hot_quota, loc_quota, quota_b, quota_p, cap) > (1 << 24):
                raise errors.TddlError(
                    "MPP hybrid join exceeds capacity ceiling")

    def _join_result(self, node, out, build, probe) -> DistBatch:
        (cols, live), out_rows, attrs = out
        MPP_JOIN_STATS[f"{node.kind}_{attrs['exchange']}"] += 1
        if attrs["exchange"] in ("shuffle", "hybrid"):
            MPP_JOIN_STATS["shuffle_build_rows"] += attrs["build_rows"]
        attrs["kind"] = node.kind
        attrs["residual"] = int(node.residual is not None)
        if node.kind in ("semi", "anti"):
            attrs["matched"] = int(out_rows.sum())  # probe rows kept
        for name, side in (("compact_b", build), ("compact_p", probe)):
            if side.compacted is not None:
                # slots a shard, in/out, and how the kept ones were found
                attrs[name] = "%d/%d:%s" % (
                    *side.compacted, exchange.compact_path(*side.compacted))
        src_meta = {fid: (typ, d)
                    for fid, typ, d in (node.left.fields() + node.right.fields())}
        out_cols = {}
        for i, (d, v) in cols.items():
            typ, dic = src_meta.get(i, (None, None))
            out_cols[i] = Column(d, v, typ, dic)
        attrs["out_fill"] = _fill(int(out_rows.sum()), int(live.shape[0]))
        return DistBatch(out_cols, live, False, shard_rows=out_rows,
                         stage_attrs=attrs)

    def _cross_attach(self, left: DistBatch, right: DistBatch) -> DistBatch:
        # 1-row replicated right side (uncorrelated scalar subquery): broadcast columns
        live_np = np.asarray(right.live)
        idx = int(live_np.argmax())
        cols = dict(left.columns)
        shape = left.live.shape
        for name, c in right.columns.items():
            d = jnp.broadcast_to(c.data[idx], shape)
            v = None if c.valid is None else jnp.broadcast_to(c.valid[idx], shape)
            cols[name] = Column(d, v, c.dtype, c.dictionary)
        return DistBatch(cols, left.live, left.replicated)

    # -- window ---------------------------------------------------------------------

    def _window(self, node: L.Window) -> DistBatch:
        """Window functions distribute by hash-repartitioning rows on the
        PARTITION BY keys, then running the scan-based window kernel per shard —
        partitions are wholly shard-local after the shuffle, so the frames are
        exact (reference: window under MPP repartitions on the partition spec)."""
        from galaxysql_tpu.exec.operators import SourceOp, WindowOp, bucket_capacity
        child = self.run(node.child)
        if child.replicated or not node.partitions:
            # a global window needs every row in one place: run the local kernel
            child = child if child.replicated else self._gather(child)
            batch = ColumnBatch(dict(child.columns), child.live)
            op = WindowOp(SourceOp([batch.pad_to(
                bucket_capacity(max(batch.capacity, 1)))]),
                node.partitions, node.orders, node.calls, out_schema=node.fields())
            out = next(iter(op.batches()))
            return DistBatch(dict(out.columns), out.live_mask(), True)

        helper = WindowOp(None, node.partitions, node.orders, node.calls)
        inputs, lanes = helper._specs()
        specs = tuple(s for _, s in lanes)
        R = int(child.live.shape[0]) // self.S
        quota = max(2 * R // self.S, 128)
        cids = list(child.columns.keys())
        while True:
            key = ("mpp_window",
                   tuple(expr_cache_key(p) for p in node.partitions),
                   tuple((expr_cache_key(e), d) for e, d in node.orders),
                   tuple(expr_cache_key(e) for e in inputs), specs,
                   tuple(cids), self.S, quota)

            def builder():
                comp = ExprCompiler(jnp)
                pfns = [comp.compile(p) for p in node.partitions]
                ofns = [(comp.compile(e), d) for e, d in node.orders]
                ifns = [comp.compile(e) for e in inputs]
                _q = quota

                def spmd(env, live):
                    # shuffle rows so each partition-key group lands on one shard
                    pk0 = [f(env) for f in pfns]
                    h = K.hash_columns([broadcast_value(live.shape[0], *kv)
                                        for kv in pk0])
                    in_pairs = [env[i] for i in cids]
                    lanes = _pack_lanes(in_pairs)
                    costs = [exchange.repartition_cost(lanes, _q)]
                    out_lanes, live_x, over = exchange.repartition_by_hash(
                        lanes, live, h, _q)
                    new_env = dict(zip(cids, _unpack_lanes(out_lanes,
                                                           in_pairs)))
                    n = live_x.shape[0]
                    pk = [broadcast_value(n, *f(new_env)) for f in pfns]
                    ok = []
                    for f, desc in ofns:
                        d, v = broadcast_value(n, *f(new_env))
                        ok.append((d, v, desc, not desc))
                    ins = [broadcast_value(n, *f(new_env)) for f in ifns]
                    order, live_s, outs = K.window_eval(pk, ok, ins, specs, live_x)
                    cols = {}
                    for i in cids:
                        d, v = new_env[i]
                        cols[i] = (d[order], None if v is None else v[order])
                    over = jax.lax.pmax(over.astype(jnp.int32),
                                        "shard").astype(jnp.bool_)
                    return (cols, live_s, outs), (
                        over, _exchange_report(costs, live_x))

                fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD, SHARD),
                               out_specs=((SHARD, SHARD, SHARD), REP),
                               check_vma=False)
                return jit_program(fn)

            (cols, live_s, outs), res = global_jit(key, builder)(child.env(),
                                                                 child.live)
            over, (vec, counts) = jax.device_get(res)
            _note_exchange(vec, counts, over)
            if not bool(over):
                break
            quota *= 2
            if quota > (1 << 24):
                raise errors.TddlError("MPP window shuffle exceeds capacity")

        out_cols = {}
        for i in cids:
            c = child.columns[i]
            d, v = cols[i]
            out_cols[i] = Column(d, v, c.dtype, c.dictionary)
        batch = helper.finalize_calls(out_cols, live_s, outs, lanes)
        return DistBatch(batch.columns, live_s, False)

    # -- union ----------------------------------------------------------------------

    def _union(self, node: L.Union) -> DistBatch:
        """UNION [ALL]: per-shard concatenation of the children (no data movement);
        UNION DISTINCT adds a group-by-all-columns dedup on top."""
        outs = [self.run(c) for c in node.children]
        first = node.children[0]
        first_ids = first.field_ids()
        fields = first.fields()
        # align column ids + dictionaries to the first child (fresh merged
        # dictionaries when children encode strings against different tables)
        aligned: List[DistBatch] = []
        out_dicts: Dict[str, Any] = {}
        for fid, typ, dic in fields:
            out_dicts[fid] = dic
        for child, b in zip(node.children, outs):
            mapping = dict(zip(child.field_ids(), first_ids))
            cols = {}
            for i, c in b.columns.items():
                fid = mapping[i]
                target = out_dicts.get(fid)
                if c.dictionary is not None and target is not None and \
                        c.dictionary is not target:
                    # translate codes into the first child's dictionary (grown
                    # with any values only the other children carry) — raw code
                    # concatenation would silently decode wrong strings
                    from galaxysql_tpu.chunk.batch import \
                        dictionary_union_translation
                    trans = dictionary_union_translation(target, c.dictionary)
                    c = Column(jnp.asarray(trans)[c.data], c.valid, c.dtype,
                               target)
                else:
                    c = Column(c.data, c.valid, c.dtype, target)
                cols[fid] = c
            aligned.append(DistBatch(cols, b.live, b.replicated))

        if any(b.replicated for b in aligned):
            host = [self._to_host(b) for b in aligned]
            from galaxysql_tpu.exec.operators import concat_batches
            merged = concat_batches(host)
            cols = {fid: Column(jnp.asarray(c.np_data()),
                                None if c.valid is None else
                                jnp.asarray(c.np_valid()), c.dtype, out_dicts[fid])
                    for fid, c in merged.columns.items()}
            result = DistBatch(cols, jnp.ones(merged.capacity, jnp.bool_)
                               if merged.capacity else jnp.zeros(0, jnp.bool_),
                               True)
        else:
            result = self._concat_shards(aligned)

        if node.all:
            return result
        groups = [(fid, ir.ColRef(fid, typ, out_dicts[fid]))
                  for fid, typ, _d in fields]
        est = sum(estimate_rows(c) for c in node.children)
        return self._aggregate_batch(result, groups, [], est)

    def _concat_shards(self, batches: List[DistBatch]) -> DistBatch:
        """Per-shard concatenation of distributed batches with identical column
        ids: shard s of the result is the concat of every input's shard s —
        a zero-communication UNION ALL."""
        ids = list(batches[0].columns.keys())
        key = ("mpp_concat", tuple(ids), len(batches), self.S,
               tuple(int(b.live.shape[0]) for b in batches))

        def builder():
            def spmd(*args):
                envs = args[::2]
                lives = args[1::2]
                cols = {}
                for fid in ids:
                    ds = [e[fid][0] for e in envs]
                    vs = [e[fid][1] for e in envs]
                    d = jnp.concatenate(ds)
                    v = None if all(x is None for x in vs) else \
                        jnp.concatenate([x if x is not None else
                                         jnp.ones(ds[k].shape[0], jnp.bool_)
                                         for k, x in enumerate(vs)])
                    cols[fid] = (d, v)
                return cols, jnp.concatenate(lives)

            n = len(batches)
            fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD,) * (2 * n),
                           out_specs=(SHARD, SHARD), check_vma=False)
            return jit_program(fn)

        flat = []
        for b in batches:
            flat += [b.env(), b.live]
        cols_o, live = global_jit(key, builder)(*flat)
        ref = batches[0].columns
        cols = {fid: Column(cols_o[fid][0], cols_o[fid][1], ref[fid].dtype,
                            ref[fid].dictionary) for fid in ids}
        return DistBatch(cols, live, False)

    def _cross_product(self, left: DistBatch, right: DistBatch) -> DistBatch:
        """General cartesian: each shard pairs its left rows with the (compacted)
        replicated right side — the filter above extracts any join predicate."""
        # compact the right side so M is the true row count, not the padding
        rb = ColumnBatch(dict(right.columns), right.live).compact()
        if rb.capacity == 0:  # empty right side: empty product, shapes kept
            shape = left.live.shape
            cols = dict(left.columns)
            for i, c in right.columns.items():
                cols[i] = Column(jnp.zeros(shape, dtype=c.data.dtype),
                                 jnp.zeros(shape, jnp.bool_), c.dtype,
                                 c.dictionary)
            return DistBatch(cols, jnp.zeros(shape, jnp.bool_), left.replicated)
        M = rb.capacity
        R = int(left.live.shape[0]) // (1 if left.replicated else self.S)
        if R * M > (1 << 22):
            raise errors.NotSupportedError(
                f"MPP cross product too large ({R}x{M} per shard)")
        lids = list(left.columns.keys())
        rids = list(rb.columns.keys())
        key = ("mpp_cross", tuple(lids), tuple(rids), R, M,
               left.replicated, self.S)

        def builder():
            def block(lenv, llive, renv, rlive):
                out = {}
                for i in lids:
                    d, v = lenv[i]
                    out[i] = (jnp.repeat(d, M),
                              None if v is None else jnp.repeat(v, M))
                for i in rids:
                    d, v = renv[i]
                    out[i] = (jnp.tile(d, R), None if v is None else
                              jnp.tile(v, R))
                live = jnp.repeat(llive, M) & jnp.tile(rlive, R)
                return out, live

            if left.replicated:
                return jit_program(block)
            fn = shard_map(block, mesh=self.mesh,
                           in_specs=(SHARD, SHARD, REP, REP),
                           out_specs=(SHARD, SHARD), check_vma=False)
            return jit_program(fn)

        renv = {i: (jnp.asarray(c.np_data()),
                    None if c.valid is None else jnp.asarray(c.np_valid()))
                for i, c in rb.columns.items()}
        rlive = jnp.ones(M, jnp.bool_) if rb.capacity else jnp.zeros(1, jnp.bool_)
        cols, live = global_jit(key, builder)(left.env(), left.live, renv, rlive)
        out_cols = {}
        for i, c in left.columns.items():
            d, v = cols[i]
            out_cols[i] = Column(d, v, c.dtype, c.dictionary)
        for i, c in rb.columns.items():
            d, v = cols[i]
            out_cols[i] = Column(d, v, c.dtype, c.dictionary)
        return DistBatch(out_cols, live, left.replicated)

    # -- sort / limit ----------------------------------------------------------------

    def _sort(self, node: L.Sort) -> DistBatch:
        child = self.run(node.child)
        if not child.replicated and node.limit is not None:
            # distributed top-n: each shard keeps only its local top
            # (limit+offset) rows before the gather — the global winners are a
            # subset of the per-shard winners (MergeSort/SpilledTopN analog)
            child = self._local_topn(node, child)
        if not child.replicated:
            child = self._gather(child)
        batch = ColumnBatch(dict(child.columns), child.live)
        op = SortOp(SourceOp([batch.pad_to(bucket_capacity(max(batch.capacity, 1)))]),
                    node.keys, node.limit, node.offset)
        out = next(iter(op.batches()))
        return DistBatch(out.columns, out.live_mask(), True)

    def _local_topn(self, node: L.Sort, child: DistBatch) -> DistBatch:
        R = int(child.live.shape[0]) // self.S
        k = min(node.limit + node.offset, R)
        if k >= R:  # nothing to cut
            return child
        cids = list(child.columns.keys())
        key = ("mpp_topn", tuple((expr_cache_key(e), d) for e, d in node.keys),
               tuple(cids), self.S, R, k)

        def builder():
            comp = ExprCompiler(jnp)
            kfns = [(comp.compile(e), d) for e, d in node.keys]

            def spmd(env, live):
                n = live.shape[0]
                keys = []
                for f, desc in kfns:
                    d, v = broadcast_value(n, *f(env))
                    # MySQL default: NULLs first ascending, last descending
                    keys.append((d, v, desc, not desc))
                order = K.sort_indices(keys, live)
                top = order[:k]
                cols = {i: (env[i][0][top],
                            None if env[i][1] is None else env[i][1][top])
                        for i in cids}
                return cols, live[top]

            fn = shard_map(spmd, mesh=self.mesh, in_specs=(SHARD, SHARD),
                           out_specs=(SHARD, SHARD), check_vma=False)
            return jit_program(fn)

        cols_o, live = global_jit(key, builder)(child.env(), child.live)
        cols = {i: Column(cols_o[i][0], cols_o[i][1], c.dtype, c.dictionary)
                for i, c in child.columns.items()}
        self.ctx.trace.append(f"mpp-topn k={k}")
        return DistBatch(cols, live, False)

    def _limit(self, node: L.Limit) -> DistBatch:
        child = self.run(node.child)
        if not child.replicated:
            child = self._gather(child)
        live = K.limit_mask(child.live, node.offset, node.limit)
        return DistBatch(child.columns, live, True)


def build_replicated_to_dist_error(node):
    raise errors.NotSupportedError("MPP join: replicated probe side unsupported")


def _agg_expr_fns(groups, inputs):
    """(group fns, input fns) for an aggregation program: compiled group-key
    and agg-input expressions, with dictionary-code inputs re-ranked for
    collation-correct min/max.  Shared by the default partial-merge round and
    the salted-repartition round."""
    comp = ExprCompiler(jnp)
    gfns = [comp.compile(e) for _, e in groups]
    ifns = []
    for e in inputs:
        f = comp.compile(e)
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
    return gfns, ifns
