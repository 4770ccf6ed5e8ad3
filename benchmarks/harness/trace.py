"""From a profiler trace to numbers: device busy time, the operations that took
most of it, collective time, and the longest idle gaps labelled with what the
host was doing.  The arithmetic works on plain event lists so that it can be
checked without a chip (`benchmarks/tests/`); `load_xplane` turns a recorded
`.xplane.pb` into those lists with `jax.profiler.ProfileData` and nothing else.

An event is `(name, start_s, end_s)` on the trace's own clock.  A trace is
`{"devices": [[event, ...] per chip, in chip order], "modules": [the same for
the chips' module lines], "host": [event, ...]}`."""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness.stats import merge_intervals

Event = Tuple[str, float, float]

WINDOW = "bench_window"          # TraceAnnotation around the traced window
STATEMENT = "bench_stmt:"        # TraceAnnotation prefix around one statement
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|collective-broadcast")
UNSAFE = re.compile(r"[^A-Za-z0-9_.:/()-]+")


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = devices if line.name == OPS_LINE else modules
                    into.setdefault(int(m.group(1)), []).extend(
                        (e.name, e.start_ns / 1e9,
                         (e.start_ns + e.duration_ns) / 1e9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns / 1e9,
                             (e.start_ns + e.duration_ns) / 1e9)
                            for e in line.events)
    chips = sorted(devices)
    return {"devices": [sorted(devices[c], key=lambda e: e[1]) for c in chips],
            "modules": [sorted(modules.get(c, []), key=lambda e: e[1])
                        for c in chips],
            "host": sorted(host, key=lambda e: e[1])}


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def window_of(trace: dict) -> Tuple[float, float]:
    """The traced window on the trace's clock: the `bench_window` annotation,
    or, where the host tracer kept none, first to last device operation."""
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
    if spans:
        return max(spans, key=lambda w: w[1] - w[0])
    ops = [e for dev in trace["devices"] for e in dev]
    if not ops:
        raise ValueError("the trace holds no device operation and no window")
    return min(s for _, s, _ in ops), max(e for _, _, e in ops)


def module_of(modules: Sequence[Event], starts: List[float], t: float) -> str:
    """`jit_run(8114710924276274526)` -> `jit_run.274526`: many programs share
    a name, and the fingerprint's tail tells them apart and stays with the
    program from run to run."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] <= t < modules[i][2]:
        return re.sub(r"\((\d+)\)$", lambda m: "." + m.group(1)[-6:],
                      modules[i][0])
    return ""


def op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line
    (`%fusion.85 = u32[6291456]{...} fusion(...)`); keep the instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """(name, start, self seconds) per event: its duration less the time of the
    events nested in it on the same line (a `while` holds its body's
    operations), so that self times add up to the busy time."""
    out: List[List] = []
    stack: List[int] = []                       # indices into out, enclosing
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= min(e, out[stack[-1]][3]) - s
        out.append([name, s, e - s, e])
        stack.append(len(out) - 1)
    return [(n, s, max(t, 0.0)) for n, s, t, _ in out]


def label(text: str) -> str:
    return UNSAFE.sub("_", text).strip("_")[:120]


def reduce_trace(trace: dict, in_flight: str = "", top: int = 10) -> dict:
    """`in_flight` labels idle gaps where no statement annotation covers them
    (a closed loop with many clients has no single statement in flight)."""
    lo, hi = window_of(trace)
    devices = [clip(dev, lo, hi) for dev in trace["devices"]]
    if not devices or not any(devices):
        raise ValueError("no operation ran on the device in the traced window")
    busy = [merge_intervals((s, e) for _, s, e in dev) for dev in devices]
    busy_s = [sum(e - s for s, e in b) for b in busy]

    # operations of chip 0 by time, named <module>/<op>
    mods = trace["modules"][0] if trace.get("modules") else []
    mod_starts = [m[1] for m in mods]
    by_name: Dict[str, float] = {}
    collective_s = 0.0
    for name, s, self_s in self_times(devices[0]):
        name = op_name(name)
        if COLLECTIVE.search(name):
            collective_s += self_s
        mod = module_of(mods, mod_starts, s)
        key = label(f"{mod}/{name}" if mod else name)
        by_name[key] = by_name.get(key, 0.0) + self_s
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

    # idle gaps of chip 0, longest first, with the statement in flight and the
    # host event that covers most of the gap
    stmts = [(n[len(STATEMENT):], s, e) for n, s, e in trace["host"]
             if n.startswith(STATEMENT)]
    host = [ev for ev in trace["host"]
            if ev[0] != WINDOW and not ev[0].startswith(STATEMENT)]
    edges = [lo] + [t for iv in busy[0] for t in iv] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top]
    idle_gaps = []
    for length, s, e in gaps:
        what = in_flight
        for name, ss, se in stmts:
            if ss <= s < se:
                what = f"{name}_at_{s - ss:.3f}s"
                break
        doing = _covering(host, s, e)
        idle_gaps.append([label(f"{what} host:{doing}" if doing else what),
                          length])
    return {"window_s": hi - lo,
            "busy_s": sum(busy_s) / len(busy_s),
            "busy_s_chip0": busy_s[0],
            "chips": len(devices),
            "collective_s_chip0": collective_s,
            "device_ops": [[n, t] for n, t in device_ops],
            "idle_gaps": idle_gaps}


def _covering(host: Sequence[Event], s: float, e: float) -> Optional[str]:
    best, best_overlap = None, 0.0
    for name, hs, he in host:
        if hs >= e:
            break
        overlap = min(he, e) - max(hs, s)
        # prefer the tightest event that still covers the most of the gap
        if overlap > best_overlap * 1.001 or (
                best is not None and overlap >= best_overlap * 0.999
                and he - hs < best[1]):
            best, best_overlap = (name, he - hs), overlap
    return best[0] if best else None
