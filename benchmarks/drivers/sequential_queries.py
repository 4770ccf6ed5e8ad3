"""Driver `sequential_queries`: one connection sends the traffic file's
statements in turn, back to back, each under the traffic's hint; the window
closes at the first statement boundary at or after `seconds`.  Every result,
warm-up and window alike, is compared in full with the deployment's plain
reference, outside the timed region."""

from __future__ import annotations

import contextlib
import os
import time


def statements_of(ctx):
    out = []
    for st in ctx.traffic["statements"]:
        with open(os.path.join(ctx.root, st["sql"])) as f:
            out.append(dict(st, text=ctx.traffic.get("hint", "") + f.read()))
    return out


def warm(ctx):
    """Executes the cell's own statements and nothing else; the first execution
    of each compiles (or loads from the persistent cache) and uploads lanes."""
    stmts = statements_of(ctx)
    ctx.deployment.prepare([st["check"] for st in stmts])
    ctx.state["statements"] = stmts
    ctx.state["conn"] = conn = ctx.served.connect(ctx.deployment.database)
    first = {}
    for i in range(ctx.traffic["warm_executions"]):
        for st in stmts:
            t0 = time.monotonic()
            rows = conn.query(st["text"])[1]
            first.setdefault(st["name"], time.monotonic() - t0)
            ctx.deployment.check(st["check"], rows)
    for st in stmts:
        st["lane_bytes"] = ctx.deployment.lane_bytes(st["reads"])
    return {"first_execution_s": first}


def measure(ctx, seconds: float, tracer):
    stmts, conn = ctx.state["statements"], ctx.state["conn"]
    trace_rounds = range(1, 1 + ctx.traffic["trace_rounds"])
    lat = {st["name"]: [] for st in stmts}
    answers = []
    traced = {"statements": 0, "client_s": 0.0, "lane_bytes": 0}
    engine0 = ctx.deployment.engine_counts()
    ctx.mark_window_start()
    t_start = time.monotonic()
    rnd, closed = 0, False
    while not closed:
        tracing = tracer.enabled and rnd in trace_rounds
        if tracing and rnd == trace_rounds[0]:
            tracer.start()
        for st in stmts:
            with tracer.statement(st["name"]) if tracing \
                    else contextlib.nullcontext():
                t0 = time.monotonic()
                rows = conn.query(st["text"])[1]
                t1 = time.monotonic()
            lat[st["name"]].append(t1 - t0)
            answers.append((st["check"], rows))
            if tracing:
                traced["statements"] += 1
                traced["client_s"] += t1 - t0
                traced["lane_bytes"] += st["lane_bytes"]
            if t1 - t_start >= seconds:
                closed = True
                break
        if tracing and (closed or rnd == trace_rounds[-1]):
            tracer.stop()
        rnd += 1
    t_end = time.monotonic()
    ctx.mark_window_end()
    engine1 = ctx.deployment.engine_counts()
    conn.close()

    failed, first_error = 0, ""
    for check, rows in answers:
        try:
            ctx.deployment.check(check, rows)
        except AssertionError as e:
            failed += 1
            first_error = first_error or str(e)[:300]
    try:
        ctx.deployment.check_engine(engine0, engine1, len(answers))
    except AssertionError as e:
        failed, first_error = max(failed, 1), first_error or str(e)
    return {"t_start": t_start, "window_s": t_end - t_start,
            "attempted": len(answers), "failed": failed,
            "first_error": first_error, "latencies_s": lat, "traced": traced,
            "in_flight": ""}
