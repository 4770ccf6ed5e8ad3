"""Driver `closed_loop`: `clients` connections, each sending its next operation
when the last was answered, with no think time, from `processes` generator
processes (`harness/loadgen.py`) that are children of the run process and
import neither the program nor JAX.  The run process only keeps time: it tells
the generators when to record, reads the program's counters at both ends of the
window, and reduces what the generators kept."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _expect(proc, word: str):
    """Blocks for one line from a generator (it answers when its phase ends; a
    dead one gives EOF at once)."""
    line = proc.stdout.readline().strip()
    if line != word:
        raise RuntimeError(f"load generator said {line!r}, expected {word!r} "
                           f"(exit code {proc.poll()})")


def _run_phase(procs, record_from: float, until: float):
    for p in procs:
        p.stdin.write(f"RUN {record_from!r} {until!r}\n")
        p.stdin.flush()


def warm(ctx):
    tr = ctx.traffic
    dep = ctx.deployment
    kind_file = os.path.join(ctx.root, "deployments", ctx.config["kind"] + ".py")
    os.makedirs(ctx.out_dir, exist_ok=True)
    params_file = os.path.join(ctx.out_dir, "loadgen_params.json")
    with open(params_file, "w") as f:
        json.dump(dep.generator_params(), f)
    per = tr["clients"] // tr["processes"]
    assert per * tr["processes"] == tr["clients"], "clients % processes != 0"
    procs, outs = [], []
    for i in range(tr["processes"]):
        out = os.path.join(ctx.out_dir, f"loadgen_{i}.json")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(ctx.root, "harness", "loadgen.py"),
             "--port", str(ctx.served.port), "--database", dep.database,
             "--deployment", kind_file, "--params", params_file,
             "--clients", str(per), "--first-client", str(i * per),
             "--seed", str(ctx.seed), "--out", out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True))
    ctx.state["procs"], ctx.state["outs"] = procs, outs
    ctx.cleanup.append(lambda: _stop(procs))
    for p in procs:
        _expect(p, "READY")
    # the loop itself is the warm-up: every batch bucket the closed loop
    # reaches is compiled by running it
    until = time.monotonic() + tr["warmup_seconds"]
    _run_phase(procs, float("inf"), until)
    for p in procs:
        _expect(p, "DONE")
    return {}


def measure(ctx, seconds: float, tracer):
    tr = ctx.traffic
    procs = ctx.state["procs"]
    t_start = time.monotonic() + tr["lead_in_seconds"]
    t_end = t_start + seconds
    _run_phase(procs, t_start, t_end)
    _sleep_until(t_start)
    ctx.mark_window_start()
    if tracer.enabled:
        _sleep_until(t_start + tr["trace_offset_seconds"])
        tracer.start()
        time.sleep(tr["trace_seconds"])
        tracer.stop()
    _sleep_until(t_end)
    ctx.mark_window_end()
    for p in procs:
        _expect(p, "DONE")
    lat, gap, failed, first_error = [], [], 0, ""
    for p in procs:
        p.stdin.write("QUIT\n")
        p.stdin.flush()
    for p, out in zip(procs, ctx.state["outs"]):
        if p.wait(60) != 0:
            raise RuntimeError(f"load generator exited with {p.returncode}")
        with open(out) as f:
            kept = json.load(f)
        lat += kept["lat_ms"]
        gap += kept["gap_ms"]
        failed += kept["failed"]
        first_error = first_error or kept["first_error"]

    # what the server itself saw, read after the window
    inst = ctx.served.instance
    elapsed = [p.elapsed_ms for p in inst.profiles.entries()
               if p.sql.lstrip().upper().startswith("SELECT")]
    conn = ctx.served.connect(ctx.deployment.database)
    try:
        batch = {r[0]: float(r[1]) for r in conn.query("SHOW BATCH STATS")[1]}
    finally:
        conn.close()
    return {"t_start": t_start, "window_s": seconds,
            "attempted": len(lat) + failed, "failed": failed,
            "first_error": first_error, "latencies_ms": lat, "gaps_ms": gap,
            "server_elapsed_ms": elapsed, "batch_stats": batch,
            "in_flight": f"{tr['clients']}_x_{tr['operation_name']}_in_flight"}


def _sleep_until(t: float):
    time.sleep(max(0.0, t - time.monotonic()))


def _stop(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
        p.wait(30)
        for pipe in (p.stdin, p.stdout):
            if pipe:
                pipe.close()
