"""Pipeline segment fusion: chains of streaming operators as ONE XLA program.

The chunk engine mirrors the reference's per-operator `nextChunk` pipeline
(SURVEY.md §2.6), but on XLA that shape is expensive: every streaming operator
(`FilterOp`, `ProjectOp`, the input side of `HashAggOp`) is its own jitted
program, so each batch pays a jax dispatch (~0.5ms) per operator and
materializes an intermediate ColumnBatch between stages.  A *segment* is the
maximal chain of streaming operators between pipeline breakers (HashAgg build,
HashJoin build, Sort, Exchange); fusing a segment into one compiled
`(columns, live) -> (computed columns, live')` program pays one dispatch per
batch and never materializes the intermediates (the Tailwind move, PAPERS.md).

Composition reuses the existing `ExprCompiler` stage lowering unchanged: a
filter stage ANDs its predicate into the live mask, a project stage rebinds the
environment — exactly what `FilterOp`/`ProjectOp` do, minus the XLA program
boundary between them.

Zero-copy passthrough (same stance as the filter-mask-only change in
`FilterOp`): the fused program returns ONLY the lanes it actually computes plus
the live mask.  Output columns that resolve to a bare input column (possibly
renamed through intermediate projects) never become XLA outputs — the host
reattaches the ORIGINAL column buffers, so a 50MB lane that merely rides
through the segment is never copied.

Cache keys are lifted (value-independent) via `LiftedLiterals`, so a
plan-cache hit on `WHERE id = ?` never retraces: the key is the stage
structure + template keys + dictionary signatures, and literal values arrive
as runtime kernel arguments.  Keys go through the process-wide `global_jit`
LRU, shared between the single-chip executor and the MPP path — the same
segment compiled once serves both.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.expr import ir
from galaxysql_tpu.expr.compiler import (ExprCompiler, LiftedLiterals,
                                         _find_dictionary, batch_env)

# kill switch: GALAXYSQL_FUSION=0 runs every streaming operator as its own
# program (the pre-fusion shape) — the A/B lever for benchmarks and the
# fused-vs-unfused equivalence suite
ENABLED = os.environ.get("GALAXYSQL_FUSION", "1") != "0"

# Stage = ("filter", ir.Expr) | ("project", [(name, ir.Expr), ...])
#       | ("rf", runtime_filter.RfStageRef)   — a planned runtime-filter
#         prelude masking a scan column against a join build side; its static
#         shape keys the program, the filter words/range are runtime args
Stage = Tuple[str, Any]

_SEGMENT_IDS = itertools.count(1)


def default_enabled(hints: Optional[dict]) -> bool:
    """Per-execution fusion decision: module switch + NO_FUSE statement hint."""
    return ENABLED and not (hints or {}).get("no_fuse", False)


def _stage_exprs(stages: Sequence[Stage]) -> List[ir.Expr]:
    out: List[ir.Expr] = []
    for kind, payload in stages:
        if kind == "filter":
            out.append(payload)
        elif kind == "project":
            out.extend(e for _, e in payload)
    return out


class FusedSegment:
    """A compiled streaming-operator chain: filter/project stages fused into
    one program per backend, plus the passthrough-column metadata the host
    needs to reattach un-computed lanes."""

    def __init__(self, stages: Sequence[Stage]):
        assert stages, "empty segment"
        self.stages: List[Stage] = list(stages)
        self.segment_id = next(_SEGMENT_IDS)
        self.chain = ">".join(kind for kind, _ in self.stages)
        exprs = _stage_exprs(self.stages)
        lift = LiftedLiterals(exprs)
        tkeys = ops.lifted_keys(lift, exprs)
        if tkeys is None:
            lift = None  # masking ambiguous: bake values (always correct)
        self.lift = lift
        self._tkeys = tkeys
        # runtime-filter prelude stages, in stage order (injected as a prefix)
        self.rf_refs = [p for k, p in self.stages if k == "rf"]
        self.rf_stage_count = len(self.rf_refs)
        # passthrough analysis: map each final output name to the INPUT column
        # it is a bare rename of, or None when it is computed.  alias=None
        # means no project stage exists: the output namespace IS the input
        # namespace and every column passes through untouched.
        alias: Optional[Dict[str, Optional[str]]] = None
        out_meta: Optional[List[Tuple[str, ir.Expr]]] = None
        for kind, payload in self.stages:
            if kind != "project":
                continue
            new_alias: Dict[str, Optional[str]] = {}
            for name, e in payload:
                if isinstance(e, ir.ColRef):
                    src = e.name if alias is None else alias.get(e.name)
                else:
                    src = None
                new_alias[name] = src
            alias = new_alias
            out_meta = list(payload)
        self.alias = alias
        self.out_meta = out_meta
        self.computed = [] if alias is None else \
            [name for name, src in alias.items() if src is None]
        # per-instance memos: segments are rebuilt per execution, so resolving
        # the global_jit entry and encoding lifted literals once per segment
        # (not once per batch) keeps the hot loop off the process-wide cache
        # lock — the per-batch overhead is exactly what this pass removes
        self._prog_memo: Dict[Tuple[bool, bool], Any] = {}
        self._lits_memo: Optional[Tuple] = None
        # EXPLAIN ANALYZE / profiling sink: when set (a list), every dispatch
        # runs the stats program variant and appends (per-stage live counts,
        # wall ms) — per-operator rows INSIDE the fused chain.  None (default)
        # keeps the production program: no extra outputs, no device syncs.
        self.stats_sink: Optional[list] = None

    # -- cache identity -----------------------------------------------------

    def key(self) -> Tuple:
        """Value-independent (when liftable) structural key for the chain."""
        parts: List[Tuple] = []
        ti = 0
        for kind, payload in self.stages:
            if kind == "rf":
                parts.append(payload.static_key())
            elif kind == "filter":
                if self._tkeys is not None:
                    k = self._tkeys[ti]
                    ti += 1
                else:
                    k = ops.expr_cache_key(payload)
                parts.append(("filter", k))
            else:
                eks = []
                for name, e in payload:
                    if self._tkeys is not None:
                        eks.append((name, self._tkeys[ti]))
                        ti += 1
                    else:
                        eks.append((name, ops.expr_cache_key(e)))
                parts.append(("project", tuple(eks)))
        return ("fused_segment", tuple(parts))

    def inert(self) -> bool:
        """True when every stage is an UNPUBLISHED runtime filter: the segment
        provably computes identity (no mask to apply, no columns computed).
        Callers use this to skip the per-batch program dispatch entirely —
        valid only after the producing join's build side has had its chance
        to publish (i.e. from the first probe batch onward)."""
        return all(k == "rf" for k, _ in self.stages) and \
            all(r.static_key()[-1] == ("off",) for r in self.rf_refs)

    def lits(self) -> Tuple:
        """(lifted literal values, per-rf-stage runtime args) — one opaque
        pytree every caller threads into the compiled program unchanged.
        Memoized per segment instance: rf args resolve at first dispatch,
        which the pull model guarantees is after the build side published."""
        if self._lits_memo is None:
            lift_vals = self.lift.values() if self.lift is not None else ()
            rf_vals = tuple(r.runtime_args() for r in self.rf_refs)
            self._lits_memo = (lift_vals, rf_vals)
        return self._lits_memo

    # -- compilation --------------------------------------------------------

    def build_apply(self, xp):
        """Stage-composition closure `(env, live, lits[, on_stage]) ->
        (env', live')`.

        Build-time only (called inside a global_jit builder, or inlined into a
        LARGER program such as HashAggOp's partial kernel — fusing scan→filter→
        project→partial-agg into one dispatch).  Returns the full final
        environment; output selection happens at the program boundary.
        `on_stage(kind, live)` fires after each stage when given — the stats
        program variant hooks per-stage live counts there; production callers
        never pass it."""
        comp = ExprCompiler(xp, lift=self.lift)
        compiled = []
        for kind, payload in self.stages:
            if kind == "rf":
                compiled.append(("rf", payload.make_fn(xp)))
            elif kind == "filter":
                compiled.append(("filter", comp.compile_predicate(payload)))
            else:
                compiled.append(
                    ("project", [(name, comp.compile(e)) for name, e in payload]))

        def apply(env, live, lits, on_stage=None):
            lift_vals, rf_vals = lits
            env = dict(env)
            env["$lits"] = lift_vals
            ri = 0
            for kind, fns in compiled:
                if kind == "rf":
                    live = fns(env, live, rf_vals[ri])
                    ri += 1
                elif kind == "filter":
                    live = live & fns(env)
                else:
                    out = {name: f(env) for name, f in fns}
                    out["$lits"] = lift_vals
                    env = out
                if on_stage is not None:
                    on_stage(kind, live)
            return env, live
        return apply

    def _program(self, jit: bool, stats: bool = False):
        """global_jit-cached fused program returning ONLY computed lanes.

        `stats=True` compiles the profiling variant, which additionally
        returns the post-stage live row count per stage (one extra int32
        reduction per stage, inside the same program) — a distinct cache key,
        so enabling profiling never perturbs the production executable."""
        f = self._prog_memo.get((jit, stats))
        if f is not None:
            return f
        backend = "jnp" if jit else "np"
        computed = list(self.computed)
        seg = self
        xp = jnp if jit else np

        def build():
            apply = seg.build_apply(xp)

            def run(env, live, lits):
                env, live = apply(env, live, lits)
                n = live.shape[0]
                out = {name: ops.broadcast_value(n, *env[name], xp=xp)
                       for name in computed}
                return out, live

            def run_stats(env, live, lits):
                n = live.shape[0]
                # counts[0] is the INPUT live count; counts[1+i] is stage i's —
                # the leading entry lets rf-stage consumers compute pruned rows
                counts = [xp.sum(xp.broadcast_to(live, (n,)).astype(xp.int32))]

                def on_stage(_kind, lv):
                    counts.append(xp.sum(
                        xp.broadcast_to(lv, (n,)).astype(xp.int32)))
                env, live = apply(env, live, lits, on_stage)
                out = {name: ops.broadcast_value(n, *env[name], xp=xp)
                       for name in computed}
                return out, live, xp.stack(counts)

            picked = run_stats if stats else run
            return ops.jit_program(picked) if jit else picked
        key = (backend, "stats" if stats else "prod") + self.key()
        # np-backend programs are plain closures — nothing to AOT-serialize,
        # so keep them out of the persistent compile cache's lookups
        f = ops.global_jit(key, build, built_flag=self._built_now, persist=jit)
        self._prog_memo[(jit, stats)] = f
        return f

    # -- execution ----------------------------------------------------------

    def _built_now(self):
        self._compiled_fresh = True

    def run_env(self, env, live, jit: bool = True):
        """Apply the segment to a raw (env, live) pair (the MPP path: lanes
        are distributed jax arrays, live is the shard-local mask)."""
        self._compiled_fresh = False
        sink = self.stats_sink
        tc = _trace_ctx()
        timed = sink is not None or tc is not None or _tracer_on()
        t0 = time.perf_counter() if timed else 0.0
        with _dispatch_annotation(tc, self.chain):
            if sink is not None:
                out, live2, counts = self._program(jit, stats=True)(
                    env, live, self.lits())
            else:
                counts = None
                out, live2 = self._program(jit)(env, live, self.lits())
        ops.DISPATCH_STATS["dispatches"] += 1
        if timed:
            wall = round((time.perf_counter() - t0) * 1000, 3)
            self._observe(tc, sink, counts, wall)
            if _tracer_on():
                self._record_span(live, live2, t0)
        return out, live2

    def _observe(self, tc, sink, counts, wall_ms: float):
        """Shared measured-dispatch bookkeeping: the wall histogram, the
        stats-sink row, and (traced queries) one child `segment` span —
        fused dispatches land as CHILDREN of the enclosing operator span
        instead of the flat per-query list profiling keeps."""
        from galaxysql_tpu.utils.metrics import SEGMENT_WALL_MS
        SEGMENT_WALL_MS.observe(wall_ms)
        if sink is not None and counts is not None:
            counts = np.asarray(counts)
            sink.append((counts, wall_ms))
        if tc is not None:
            from galaxysql_tpu.utils import tracing as _tr
            attrs = {"compiled": self._compiled_fresh,
                     "segment_id": self.segment_id}
            if counts is not None:
                attrs["rows_in"] = int(counts[0])
                attrs["rows_out"] = int(counts[-1])
            tc.add(f"segment:{self.chain}", kind="segment",
                   start_us=_tr.now_us() - int(wall_ms * 1000),
                   dur_us=wall_ms * 1000, **attrs)

    def attach_columns(self, src_columns: Dict[str, Column],
                       out: Dict[str, Any]) -> Dict[str, Column]:
        """Final output columns: computed lanes from the program, passthrough
        lanes reattached from the ORIGINAL input buffers (zero-copy)."""
        if self.alias is None:
            return dict(src_columns)  # no project stage: identity namespace
        cols: Dict[str, Column] = {}
        for name, e in self.out_meta:
            src = self.alias[name]
            if src is not None:
                c0 = src_columns[src]
                cols[name] = Column(c0.data, c0.valid, c0.dtype, c0.dictionary)
            else:
                d, v = out[name]
                cols[name] = Column(d, v, e.dtype, _find_dictionary(e))
        return cols

    def run_batch(self, batch: ColumnBatch) -> ColumnBatch:
        """Apply the segment to one ColumnBatch (single-chip executor path).

        Mirrors FilterOp/ProjectOp backend selection: small all-host batches
        (TP point queries) run the np expression backend directly — per-call
        jax dispatch dwarfs the work at point-query sizes."""
        host = batch.capacity <= ops.TP_HOST_ROWS and ops._is_host_batch(batch)
        self._compiled_fresh = False
        sink = self.stats_sink
        tc = _trace_ctx()
        timed = sink is not None or tc is not None or _tracer_on()
        t0 = time.perf_counter() if timed else 0.0
        counts = None
        with _dispatch_annotation(tc, self.chain):
            if host:
                env = {n: (c.data, c.valid) for n, c in batch.columns.items()}
                live_in = batch.live if batch.live is not None else \
                    np.ones(batch.capacity, np.bool_)
                f = self._program(False, stats=sink is not None)
                if sink is not None:
                    out, live, counts = f(env, live_in, self.lits())
                else:
                    out, live = f(env, live_in, self.lits())
                live = np.broadcast_to(np.asarray(live), (batch.capacity,))
            else:
                f = self._program(True, stats=sink is not None)
                if sink is not None:
                    out, live, counts = f(batch_env(batch), batch.live_mask(),
                                          self.lits())
                else:
                    out, live = f(batch_env(batch), batch.live_mask(),
                                  self.lits())
        ops.DISPATCH_STATS["dispatches"] += 1
        if timed:
            wall = round((time.perf_counter() - t0) * 1000, 3)
            self._observe(tc, sink, counts, wall)
            if _tracer_on():
                self._record_span(batch.live_mask(), live, t0)
        return ColumnBatch(self.attach_columns(batch.columns, out), live)

    def run_live_np(self, batch: ColumnBatch) -> np.ndarray:
        """Host-np live mask for `batch` with the segment's stages applied —
        the np twin of the in-kernel mask composition.  Used by the native and
        grace-spill join paths, where the probe prelude is filter-only and
        only the mask (not the env) is consumed."""
        env = {n: (c.np_data(), None if c.valid is None else c.np_valid())
               for n, c in batch.columns.items()}
        _out, live = self._program(False)(env, batch.np_live(), self.lits())
        return np.broadcast_to(np.asarray(live), (batch.capacity,))

    def _record_span(self, live_in, live_out, t0: float):
        from galaxysql_tpu.utils.tracing import SEGMENT_TRACER, SegmentSpan
        SEGMENT_TRACER.record(SegmentSpan(
            segment_id=self.segment_id, chain=self.chain,
            rows_in=int(np.asarray(live_in).sum()),
            rows_out=int(np.asarray(live_out).sum()),
            compiled=self._compiled_fresh,
            wall_ms=round((time.perf_counter() - t0) * 1000, 3)))


def _tracer_on() -> bool:
    from galaxysql_tpu.utils.tracing import SEGMENT_TRACER
    return SEGMENT_TRACER.active  # a query-scoped sink on this thread


def _trace_ctx():
    """The thread's active TraceContext (span tracing), or None."""
    from galaxysql_tpu.utils import tracing
    return tracing.current()


def _dispatch_annotation(tc, chain: str):
    """`segment:<chain>` around one dispatch while a profiler session records
    the statement; nothing otherwise."""
    from galaxysql_tpu.utils import tracing
    if tc is None or not tc.annotate:
        return tracing.NO_ANNOTATION
    return tc.annotation("segment:" + chain)


class FusedPipelineOp(ops.Operator):
    """Streaming operator applying one FusedSegment per batch — replaces a
    stack of FilterOp/ProjectOp instances with a single program dispatch."""

    def __init__(self, child: ops.Operator, segment: FusedSegment, ctx=None):
        self.child = child
        self.segment = segment
        self.ctx = ctx  # ExecContext (deadline checks); None in unit tests

    def _gate(self):
        # fused-segment dispatch boundary: a MAX_EXECUTION_TIME deadline
        # aborts typed BEFORE the next program dispatch (None = one attr read)
        if self.ctx is not None:
            self.ctx.check_deadline()

    def batches(self):
        it = self.child.batches()
        first = next(it, None)
        if first is None:
            return
        if self.segment.inert():
            # rf-only segment whose filters never published (grace-spilled or
            # oversized build, deactivated edge): pure passthrough — don't
            # pay a per-batch identity-program dispatch
            yield first
            yield from it
            return
        self._gate()
        yield self.segment.run_batch(first)
        for b in it:
            self._gate()
            yield self.segment.run_batch(b)


def segment_for(node, min_stages: int = 1, filters_only: bool = False,
                rf=None):
    """Shared collapse-into-segment wiring for the local and MPP engines:
    (base node, FusedSegment | None).  Returns a segment only when the chain
    above `node` has at least `min_stages` stages (and, with `filters_only`,
    no project stage — the join-probe case, where a project would change the
    column namespace the join gathers from); otherwise (node, None).

    `rf` (a runtime_filter.RuntimeFilterManager) injects the base scan's
    planned runtime filters as ("rf", …) prelude stages INSIDE the segment —
    one program applies filter-pushdown + the streaming chain in a single
    dispatch — and marks the scan consumed so the scan-level fallback
    (plan/physical._wrap_scan_rf, parallel/mpp._scan) skips it."""
    stages, base = collapse_streaming_chain(node)
    rf_stages = rf.stages_for(base) if rf is not None else []
    if rf_stages and rf.consumed(base):
        rf_stages = []
    all_stages = rf_stages + stages
    if len(all_stages) < min_stages:
        return node, None
    if filters_only and any(kind == "project" for kind, _ in all_stages):
        return node, None
    if rf_stages:
        rf.mark_consumed(base)
    return base, FusedSegment(all_stages)


def chain_nodes(node) -> List[Any]:
    """The logical Filter/Project nodes a segment built from `node` covers, in
    STAGE ORDER (bottom-up — stage i of the FusedSegment is node i here).
    Profiling uses this to attribute per-stage live counts back to the plan
    nodes EXPLAIN ANALYZE renders."""
    from galaxysql_tpu.plan import logical as L
    out: List[Any] = []
    cur = node
    while isinstance(cur, (L.Filter, L.Project)):
        out.append(cur)
        cur = cur.child
    out.reverse()
    return out


def collapse_streaming_chain(node) -> Tuple[List[Stage], Any]:
    """Maximal chain of streaming logical nodes above `node`'s first pipeline
    breaker: (bottom-up stages, base node).  Streaming = Filter/Project; every
    other node (Scan, Aggregate build, Join build, Sort, Exchange/shuffle,
    Window, Limit, Union) is a segment boundary."""
    from galaxysql_tpu.plan import logical as L
    rev: List[Stage] = []
    cur = node
    while isinstance(cur, (L.Filter, L.Project)):
        if isinstance(cur, L.Filter):
            rev.append(("filter", cur.cond))
        else:
            rev.append(("project", list(cur.exprs)))
        cur = cur.child
    rev.reverse()
    return rev, cur
