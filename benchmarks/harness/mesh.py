"""The mesh cell's arithmetic: what the four chips' planes of a profiler trace
say about the exchange (collective time in flight, the part of it that is
exposed, how evenly the chips are busy), and what the program's own
`EXCHANGE_STATS` counter says left a chip.  The arithmetic works on the plain
event lists of `trace.py` and is checked without a chip
(`benchmarks/tests/test_mesh.py`).  On a commit whose program keeps no such
counter every function here returns `None`; nothing raises."""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import spans, trace as T
from benchmarks.harness.stats import merge_intervals

# A device event is named by its whole HLO line.  The instruction's own name
# need not say what it is: JAX's `all_to_all` reaches the chip as
# `%all_to_all.105 = u32[4,1,1048576]{...} all-to-all(%bitcast.141), ...`, so a
# collective is told by its opcode; a bare name (a hand-made list) by itself.
OPCODE = re.compile(r"(?:^|[\s)])((?:all-to-all|all-gather|all-reduce|"
                    r"reduce-scatter|collective-permute|collective-broadcast)"
                    r"(?:-start|-done)?)\(")
ASYNC = re.compile(r"^(.*)-(start|done)$")


def collective_of(name: str) -> Optional[str]:
    """`all-to-all`, `all-gather-start`, ... for a collective's event; `None`
    for any other operation."""
    if " = " in name:
        m = OPCODE.search(name.split(" = ", 1)[1])
        return m.group(1) if m else None
    inst = re.sub(r"\.\d+$", "", T.op_name(name))
    return inst if T.COLLECTIVE.search(inst) else None


def exchange_stats() -> Optional[Dict[str, int]]:
    """The program's cumulative exchange counters (`parallel/mpp.py`), or
    `None` where the commit has none or no MPP statement ran."""
    try:
        from galaxysql_tpu.parallel import mpp
    except Exception:
        return None
    stats = getattr(mpp, "EXCHANGE_STATS", None)
    if not stats or not stats.get("statements"):
        return None
    return dict(stats)


def statements_sent(run) -> Optional[int]:
    """The cell's own statements this process has answered, warm-up and window.
    The counters are cumulative from process start, and set-up's `COUNT(*)`
    over `lineitem` runs on the mesh too: it would count as a statement and
    adds about 20 KB of gathered partials, so the per-statement values divide
    by what the driver sent instead of by the counter's `statements`."""
    try:
        warm = run.traffic["warm_executions"] * len(run.traffic["statements"])
        return (run.window["attempted"] + warm) or None
    except (KeyError, TypeError):
        return None


def exchange_bytes_per_stmt(stats: Dict[str, int], statements: int) -> float:
    """Bytes one shard handed to `all_to_all` and `all_gather`, a statement."""
    return (stats["all_to_all_bytes"] + stats["all_gather_bytes"]) / statements


def ici_bytes_out(exchange_bytes: float, chips: int) -> float:
    """Bytes that left one chip over the interconnect, of `exchange_bytes` a
    shard handed over: of a send buffer of S destination blocks, and of a
    gathered result of S source blocks, one block is the chip's own and never
    travels."""
    return exchange_bytes * (chips - 1) / chips


def ici_floor_s(bytes_out: float, ici_bits_per_s: float) -> float:
    """The least time the chip's links could take to carry `bytes_out`."""
    return bytes_out * 8.0 / ici_bits_per_s


def leaves(events: Sequence[T.Event]) -> List[T.Event]:
    """The events that hold no other event of the line (a `while` holds its
    body; an operation that a copy runs inside is not a leaf)."""
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= ev[2]:
            out.append(ev)
    return out


def in_flight(events: Sequence[T.Event]) -> List[Tuple[float, float]]:
    """[start, end) of every collective: the operation's own interval, or, for
    an asynchronous one, from its `-start` to the end of the `-done` that
    names it (else the next `-done` of its kind)."""
    spans: List[Tuple[float, float]] = []
    open_starts: Dict[str, Tuple[str, float]] = {}      # instruction -> kind, t
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        what = collective_of(name)
        if what is None:
            continue
        m = ASYNC.match(what)
        if not m:
            spans.append((s, e))
        elif m.group(2) == "start":
            open_starts[T.op_name(name)] = (m.group(1), s)
        else:
            named = [r for r in re.findall(r"%([A-Za-z0-9_.-]+)",
                                           name.split(" = ", 1)[-1])
                     if r in open_starts]
            key = named[0] if named else next(
                (k for k, (kind, _) in open_starts.items()
                 if kind == m.group(1)), None)
            t0 = open_starts.pop(key)[1] if key is not None else s
            spans.append((t0, e))
    spans.extend((t, t) for _, t in open_starts.values())  # never done: empty
    return spans


def reduce_mesh(trace: dict) -> Optional[dict]:
    """`{"busy_s": [seconds per chip], "collective_in_flight_s",
    "collective_exposed_s"}` of the traced window; the collective numbers are
    chip 0's.  Exposed is the part of the time a collective is in flight during
    which chip 0 runs no other operation (the `on-chip-measurement` guide's
    definition): for a synchronous collective all of its self time, for an
    asynchronous one what `-start`, `-done` and the gaps between them take."""
    lo, hi = T.window_of(trace)
    devices = [T.clip(dev, lo, hi) for dev in trace["devices"]]
    if not devices or not any(devices):
        return None
    busy = [sum(e - s for s, e in merge_intervals((s, e) for _, s, e in dev))
            for dev in devices]
    flight = merge_intervals(in_flight(devices[0]))
    compute = merge_intervals(
        (s, e) for n, s, e in leaves(devices[0]) if collective_of(n) is None)
    return {"busy_s": busy,
            "collective_in_flight_s": sum(e - s for s, e in flight),
            "collective_exposed_s": spans.idle_inside(
                compute, [("", s, e, 0) for s, e in flight])}


def busy_max_over_mean(busy_s: Sequence[float]) -> Optional[float]:
    mean = sum(busy_s) / len(busy_s) if busy_s else 0.0
    return max(busy_s) / mean if mean > 0 else None


def of_run(run) -> Optional[dict]:
    """`reduce_mesh` of the traced run's recorded trace, once a run; `None` for
    an untraced run."""
    if run.trace is None:
        return None
    if "mesh" not in run.state:
        path = T.newest_xplane(os.path.join(run.out_dir, "trace"))
        run.state["mesh"] = reduce_mesh(T.load_xplane(path))
    return run.state["mesh"]


def traced_statements(run) -> int:
    traced = run.window.get("traced")
    return traced["statements"] if traced else 0
