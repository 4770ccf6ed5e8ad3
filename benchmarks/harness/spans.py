"""The program's own spans and its program families, read from a profiler trace.

While a `jax.profiler` session records, the program enters its spans as
annotations (`galaxysql_tpu/utils/tracing.py`): `phase:<name>`, `op:<RelNode>`,
`segment:<chain>`, `compile:<family>`, `transfer:<table>`, `stage:`/`shard:` on
the mesh, each with the statement's `trace_id`; and every `global_jit` program's
HLO module is named `jit_<family>`.  This file turns a recorded `.xplane.pb`
into plain lists and holds the arithmetic on them, which is checked without a
chip (`benchmarks/tests/test_spans.py`).  A trace of a program that enters no
span and names no family (an older commit) gives empty lists, and the readers
built on them return `None` or count every module as unnamed; nothing raises.

A span is `(name, start_s, end_s, trace_id)` on the trace's clock, one list per
host thread (spans nest within a thread).  An operation or a module is
`(name, start_s, end_s)` as in `trace.py`."""

from __future__ import annotations

import bisect
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import trace as T
from benchmarks.harness.stats import merge_intervals

Span = Tuple[str, float, float, int]

# what the program enters; the profiler's own events (PjitFunction, np.asarray)
# are not spans of the program
SPAN_NAME = re.compile(r"^(phase|op|segment|compile|transfer|stage|shard|rpc|"
                       r"worker):|^query$")
MODULE = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")

GROUPS = ("join", "agg", "sort", "scan")

# Every family the program can build (the first element of a `global_jit` key;
# fused segments are `segment`), each in exactly one group.  `tests/
# test_program_names.py` holds this table equal to the program's source.
FAMILY_GROUP: Dict[str, str] = {
    # local executor (exec/operators.py, exec/fusion.py)
    "join_pairs": "join", "join_build_slots": "join", "join_probe_csr": "join",
    "join_gather": "join", "bloom_dev": "join", "bloom_query": "join",
    "agg_partial": "agg", "agg_merge": "agg",
    "sort": "sort", "window": "sort",
    "filter": "scan", "project": "scan", "segment": "scan",
    "filter_np": "scan", "project_np": "scan",   # host closures: no module
    # a batched point lookup probes one table's sorted key lane: scan work
    "batch_point": "scan",
    # Pallas tier (kernels/pallas_*.py), where its work is
    "pallas_join_slots": "join", "pallas_join_expand": "join",
    "pallas_agg_place": "agg",
    # MPP executor (parallel/mpp.py)
    "mpp_filter": "scan", "mpp_project": "scan", "mpp_concat": "scan",
    "mpp_agg": "agg", "mpp_agg_salt": "agg",
    "mpp_bjoin": "join", "mpp_sjoin": "join", "mpp_hybrid_join": "join",
    "mpp_cross": "join",
    "mpp_window": "sort", "mpp_topn": "sort",
}


# -- from a recorded trace to lists ------------------------------------------------


def load(path: str) -> dict:
    """`{"threads": [[span, ...] per host thread], "statements": [event, ...],
    "host": [event, ...], "ops": [...], "modules": [...]}`; operations and
    modules are chip 0's."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    threads: List[List[Span]] = []
    statements: List[T.Event] = []
    host: List[T.Event] = []
    ops: List[T.Event] = []
    modules: List[T.Event] = []
    for plane in data.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            for line in plane.lines:
                if line.name in (T.OPS_LINE, T.MODULES_LINE):
                    into = ops if line.name == T.OPS_LINE else modules
                    into.extend((e.name, e.start_ns / 1e9,
                                 (e.start_ns + e.duration_ns) / 1e9)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans: List[Span] = []
                for e in line.events:
                    ev = (e.name, e.start_ns / 1e9,
                          (e.start_ns + e.duration_ns) / 1e9)
                    if SPAN_NAME.match(e.name):
                        spans.append(ev + (_trace_id(e),))
                    elif e.name.startswith(T.STATEMENT):
                        statements.append(ev)
                    elif e.name == T.WINDOW:
                        host.append(ev)
                if spans:
                    threads.append(sorted(spans, key=lambda s: (s[1], -s[2])))
    return {"threads": threads,
            "statements": sorted(statements, key=lambda e: e[1]),
            "host": host,
            "ops": sorted(ops, key=lambda e: e[1]),
            "modules": sorted(modules, key=lambda e: e[1])}


def _trace_id(event) -> int:
    for key, value in event.stats:
        if key == "trace_id":
            return int(value)
    return 0


def of_run(run) -> Optional[dict]:
    """The traced run's spans, clipped to its window and reduced once (every
    reader of one run shares the result); `None` for an untraced run."""
    if run.trace is None:
        return None
    if "spans" not in run.state:
        raw = load(T.newest_xplane(os.path.join(run.out_dir, "trace")))
        run.state["spans"] = reduce_spans(raw)
    return run.state["spans"]


def reduce_spans(raw: dict) -> dict:
    lo, hi = T.window_of({"host": raw["host"], "devices": [raw["ops"]]})
    ops = T.clip(raw["ops"], lo, hi)
    threads = [[s for s in thread if s[2] > lo and s[1] < hi]
               for thread in raw["threads"]]
    busy = merge_intervals((s, e) for _, s, e in ops)
    execute = [s for th in threads for s in th if s[0] == "phase:execute"]
    return {"threads": threads,
            "statements": [e for e in raw["statements"]
                           if e[2] > lo and e[1] < hi],
            "plan_self_s": self_seconds(threads, "phase:plan"),
            "serialize_s": self_seconds(threads, "phase:serialize"),
            "execute_spans": len(execute),
            "execute_idle_s": idle_inside(busy, execute),
            "families": family_seconds(ops, raw["modules"]),
            "busy_s": sum(e - s for s, e in busy)}


# -- arithmetic on plain lists -----------------------------------------------------


def self_seconds(threads: Sequence[Sequence[Span]], name: str) -> Optional[float]:
    """Seconds in spans called `name`, less the program's other spans nested in
    them on the same thread (a subquery executed while planning is operator
    time, not planner time); `None` where no such span was entered."""
    total, found = 0.0, False
    for thread in threads:
        for _, s, e, _ in (sp for sp in thread if sp[0] == name):
            found = True
            inner = merge_intervals(
                (max(cs, s), min(ce, e)) for cn, cs, ce, _ in thread
                if cs >= s and ce <= e and not (cn == name and cs == s
                                                and ce == e))
            total += (e - s) - sum(b - a for a, b in inner)
    return total if found else None


def idle_inside(busy: Sequence[Sequence[float]],
                spans: Sequence[Span]) -> Optional[float]:
    """Seconds of `spans` in which the chip ran nothing: `busy` is the merged
    list of its busy intervals."""
    if not spans:
        return None
    starts = [b[0] for b in busy]
    idle = 0.0
    for _, s, e, _ in spans:
        covered = 0.0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0.0, min(busy[i][1], e) - max(busy[i][0], s))
            i += 1
        idle += (e - s) - covered
    return idle


def family_of(module: str) -> str:
    """`jit_join_pairs(8114710924276274526)` -> `join_pairs`; '' for a name
    that is no jitted module's."""
    m = MODULE.match(module)
    return m.group(1) if m else ""


def family_seconds(ops: Sequence[T.Event], modules: Sequence[T.Event]) -> dict:
    """Self seconds of chip 0's operations by group of the module each ran in:
    `{"join": s, "agg": s, "sort": s, "scan": s, "unnamed": s, "by_family":
    {family or module name: s}}`.  The five add up to the busy time."""
    starts = [m[1] for m in modules]
    out = {g: 0.0 for g in GROUPS + ("unnamed",)}
    by_family: Dict[str, float] = {}
    for _, s, self_s in T.self_times(ops):
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][0] if i >= 0 and s < modules[i][2] else ""
        family = family_of(module)
        group = FAMILY_GROUP.get(family, "unnamed")
        out[group] += self_s
        key = family if group != "unnamed" else (module or "no_module")
        by_family[key] = by_family.get(key, 0.0) + self_s
    out["by_family"] = by_family
    return out


def per_statement(run, what: str, group: Optional[str] = None) -> Optional[float]:
    """`reduce_spans(...)[what]` (or `["families"][group]`) per traced statement;
    `None` for an untraced run and where the program entered no such span."""
    traced = run.window.get("traced")
    spans = of_run(run)
    if spans is None or not traced or not traced["statements"]:
        return None
    value = spans[what][group] if group else spans[what]
    return None if value is None else value / traced["statements"]
