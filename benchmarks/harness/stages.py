"""Device seconds by stage: chip 0's operations of the traced window, each
named after the `jax.named_scope` of the program that built it.

The program keeps a registry of the programs it built (`galaxysql_tpu/exec/
programs.py`: family, key digest, input slots, the span that launched the first
call) and can say, for each, which stage every instruction of its compiled
module belongs to.  This file reads that registry from the run's own process
AFTER the window (as `local_joins.py` reads `JOIN_STATS`), asks for the stages
of the programs whose families the trace shows, matches each module of the
trace to a program and adds up seconds by stage.  The arithmetic works on plain
lists and is checked without a chip (`benchmarks/tests/test_stages.py`).  On a
commit whose program keeps no registry `of_run` returns `None` and every reader
leaves its metric out; nothing raises.

A module of the trace is `jit_<family>(<n>)`.  No number the executable gives
out equals `<n>` (tried on a v5e: the 32 bytes of `fingerprint`, word by word
in either byte order, and `HloModuleProto.id`), so a module is matched as the
builders' hand wrappers did: to the programs of its family whose compiled text
knows the instructions that hold at least `COVERAGE` of the module's seconds.
An instruction is told by its name and the arrays of its result (a trace names
an event by its whole HLO line), by its name alone where that finds nothing.
The best coverage wins; programs that tie must split the module's seconds
alike, else the whole module counts as unmatched: never guessed."""

from __future__ import annotations

import bisect
import json
import os
import re
import time
from typing import Callable, Dict, Optional, Sequence

from benchmarks.harness import spans, trace as T

COVERAGE = 0.9
# lowering a program again hits JAX's caches (milliseconds); a machine whose
# cache evicted it would compile for minutes: programs past this many seconds
# are not asked, and their modules count as unmatched
LOWERING_BUDGET_S = 30.0

JOIN_LOOKUP = ("join_pairs/sort", "join_pairs/probe", "join_pairs/front")
JOIN_EXPAND = ("join_pairs/expand",)
JOIN_VERIFY = ("join_pairs/verify",)
GROUPBY_SORT = ("groupby/sort",)
GROUPBY_BOUNDARIES = ("groupby/boundaries",)
GROUPBY_REDUCE = ("groupby/reduce",)
REPARTITION = ("exchange/repartition",)
COMPACT = ("exchange/compact",)


# -- the program's registry, as plain data -----------------------------------------


def registry():
    """`galaxysql_tpu.exec.programs`, or `None` on a commit that has none."""
    try:
        from galaxysql_tpu.exec import programs
    except Exception:
        return None
    return programs if hasattr(programs, "PROGRAMS") else None


def programs_of(families: Sequence[str]) -> Optional[dict]:
    """`{"programs": [{family, program, slots, span, trace_id, instructions}],
    "entries", "bytes", "lowered", "lower_s"}`: the registry's entries of
    `families` with their instruction -> stage maps (`None` where the program
    has none to give, `note` says why)."""
    reg = registry()
    if reg is None:
        return None
    entries = reg.PROGRAMS.entries()
    size = sum(len(repr((p.family, p.digest, p.slots, p.signature, p.unsigned,
                         p.first_call_ms, p.span, p.trace_id)))
               for p in entries)
    out, spent = [], 0.0
    for p in entries:
        if p.family not in families:
            continue
        asked = p.stages is not None or bool(p.unstaged)
        got, note = None, "over the lowering budget"
        if asked or spent < LOWERING_BUDGET_S:
            t0 = time.perf_counter()
            got = reg.PROGRAMS.stages(p)
            spent += 0.0 if asked else time.perf_counter() - t0
            note = p.unstaged
        out.append({"family": p.family, "program": p.digest,
                    "slots": list(p.slots), "span": p.span,
                    "trace_id": p.trace_id, "note": note,
                    "instructions": got})
    return {"programs": out, "entries": len(entries), "bytes": size,
            "lowered": sum(1 for p in out if p["instructions"] is not None),
            "lower_s": spent}


# -- arithmetic on plain lists -----------------------------------------------------


def seconds_by_module(ops: Sequence[T.Event],
                      modules: Sequence[T.Event]) -> Dict[str, Dict[str, float]]:
    """Module event name ('' outside any) -> operation's whole name -> self
    seconds."""
    starts = [m[1] for m in modules]
    out: Dict[str, Dict[str, float]] = {}
    for name, s, self_s in T.self_times(ops):
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][0] if i >= 0 and s < modules[i][2] else ""
        by_op = out.setdefault(module, {})
        by_op[name] = by_op.get(name, 0.0) + self_s
    return out


def match(by_op: Dict[str, float], family: str, candidates: Sequence[dict],
          key_of: Callable[[str], Optional[str]]) -> dict:
    """The module whose operations took `by_op` seconds against the programs
    of its family: `{"program": the matched entry or None, "stages": {stage:
    seconds} or None, "candidates", "coverage", "by"}`."""
    total = sum(by_op.values()) or 1e-30
    none = f"{family}/-"
    for by, key in (("name and result", key_of),
                    ("name", lambda n: (key_of(n) or "").split(" ")[0])):
        keyed: Dict[str, float] = {}
        for name, t in by_op.items():
            k = key(name) or name
            keyed[k] = keyed.get(k, 0.0) + t
        scored = []
        for c in candidates:
            known = c["instructions"]
            if by == "name":
                known = {k.split(" ")[0]: v for k, v in known.items()}
            covered = sum(t for k, t in keyed.items() if k in known) / total
            if covered >= COVERAGE:
                scored.append((covered, c, known))
        if scored:
            break
    if not scored:
        return {"program": None, "stages": None, "candidates": 0,
                "coverage": 0.0, "by": ""}
    best = max(s[0] for s in scored)
    tied = [s for s in scored if s[0] >= best - 1e-9]
    splits = []
    for _, c, known in tied:
        split: Dict[str, float] = {}
        for k, t in keyed.items():
            stage = known.get(k, none)
            split[stage] = split.get(stage, 0.0) + t
        splits.append(split)
    agree = all(_same(splits[0], s) for s in splits[1:])
    return {"program": tied[0][1] if agree else None,
            "stages": splits[0] if agree else None,
            "candidates": len(tied), "coverage": best, "by": by}


def short_name(module: str) -> str:
    """`jit_join_pairs(8114710924276274526)` -> `jit_join_pairs.274526`, as
    `trace.py:module_of` names a module on the result line."""
    return re.sub(r"\((\d+)\)$", lambda m: "." + m.group(1)[-6:],
                  module) or "no_module"


def _same(a: Dict[str, float], b: Dict[str, float]) -> bool:
    return set(a) == set(b) and all(abs(a[k] - b[k]) <= 1e-9 for k in a)


def reduce_stages(ops: Sequence[T.Event], modules: Sequence[T.Event],
                  programs: Sequence[dict],
                  key_of: Callable[[str], Optional[str]] = T.op_name) -> dict:
    """`{"busy_s", "stages": {stage: seconds}, "unmatched_s", "modules":
    [...]}` of chip 0's operations (clipped to the window by the caller).  The
    stages and the unmatched seconds add up to the busy seconds."""
    stages: Dict[str, float] = {}
    unmatched = 0.0
    rows = []
    for module, by_op in seconds_by_module(ops, modules).items():
        family = spans.family_of(module)
        seconds = sum(by_op.values())
        got = match(by_op, family,
                    [p for p in programs if p["family"] == family
                     and p["instructions"] is not None], key_of)
        row = {"module": short_name(module), "seconds": seconds,
               "family": family,
               "candidates": got["candidates"], "coverage": got["coverage"],
               "by": got["by"]}
        if got["stages"] is None:
            unmatched += seconds
        else:
            for stage, t in got["stages"].items():
                stages[stage] = stages.get(stage, 0.0) + t
            p = got["program"]
            row.update(program=p["program"], slots=p["slots"], span=p["span"],
                       trace_id=p["trace_id"], stages=got["stages"])
        rows.append(row)
    rows.sort(key=lambda r: -r["seconds"])
    return {"busy_s": sum(stages.values()) + unmatched, "stages": stages,
            "unmatched_s": unmatched, "modules": rows}


# -- one run -----------------------------------------------------------------------


def of_run(run) -> Optional[dict]:
    """`reduce_stages` of the traced run, made once and written whole to
    `<out_dir>/stages.json`; `None` for an untraced run and on a program
    without a registry."""
    if run.trace is None or registry() is None:
        return None
    if "stages" not in run.state:
        raw = spans.load(T.newest_xplane(os.path.join(run.out_dir, "trace")))
        lo, hi = T.window_of({"host": raw["host"], "devices": [raw["ops"]]})
        ops = T.clip(raw["ops"], lo, hi)
        families = {spans.family_of(m[0]) for m in raw["modules"]
                    if m[2] > lo and m[1] < hi}
        known = programs_of(families)
        got = reduce_stages(ops, raw["modules"], known.pop("programs"),
                            registry().instruction_key)
        traced = run.window.get("traced") or {}
        got.update(registry=known, statements=traced.get("statements", 0))
        with open(os.path.join(run.out_dir, "stages.json"), "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
        run.state["stages"] = got
    return run.state["stages"]


def per_statement(run, names: Sequence[str]) -> Optional[float]:
    """Seconds in the stages `names`, summed, per traced statement."""
    got = of_run(run)
    if got is None or not got["statements"]:
        return None
    return sum(got["stages"].get(n, 0.0) for n in names) / got["statements"]


def unstaged_share(run) -> Optional[float]:
    """Percent of chip 0's busy seconds in instructions of matched modules
    that sit under no stage (`<family>/-`)."""
    got = of_run(run)
    if got is None or not got["busy_s"]:
        return None
    loose = sum(t for s, t in got["stages"].items() if s.endswith("/-"))
    return 100.0 * loose / got["busy_s"]


def unmatched_share(run) -> Optional[float]:
    """Percent of chip 0's busy seconds in modules no registry entry matched
    (or two that split them differently)."""
    got = of_run(run)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * got["unmatched_s"] / got["busy_s"]
