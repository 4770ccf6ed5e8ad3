#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process boots `Instance` + `MySQLServer` on port 0 (as `net/server.py:main`
does), makes the cell's data from `--seed`, loads it in bulk, warms exactly the
cell's statements and checks them against the plain reference (all of that is
`setup_s`), measures for `--seconds`, and prints the result object as the last
line of stdout.  Every statement travels over a socket through the benchmark's
own wire client.  Everything that belongs to one configuration, traffic mix,
deployment kind, driver or metric is a file found by its name (README.md); this
file knows none of them.

It refuses any platform but `tpu` and any device count but the cell's `chips`.
`--dry-run-cpu` is the explicit rehearsal mode for a machine without a chip:
tiny scale, the result line is marked `dry_run` and carries counts only."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
sys.path.insert(0, CHECKOUT)

from benchmarks.harness import stats, trace as T  # noqa: E402
from benchmarks.harness.byname import load_module  # noqa: E402


def named(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`."""
    return load_module(os.path.join(ROOT, kind, name + ".py"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_of(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"run.py: BENCHMARK.json has no workload {workload!r} "
                     f"(has {[w['name'] for w in bench['workloads']]})")


def metrics_of(bench: dict, group: str, workload: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


class Tracer:
    """`jax.profiler` around part of the window, host annotations included, the
    Python tracer off (it would record every call of the server's threads)."""

    def __init__(self, enabled: bool, log_dir: str):
        self.enabled = enabled
        self.log_dir = log_dir
        self.ran = False

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(self.log_dir, ignore_errors=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = self._annotation(T.WINDOW)
        self._window.__enter__()

    def stop(self):
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.ran = True

    def statement(self, name: str):
        return self._annotation(T.STATEMENT + name)

    def _annotation(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class Context:
    """What a deployment kind, a driver and a metric reader are handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.state = {}
        self.cleanup = []
        self.counters_start = self.counters_end = None

    def mark_window_start(self):
        self.counters_start = counters()

    def mark_window_end(self):
        self.counters_end = counters()


def counters() -> dict:
    """The program's own counters, read as they are."""
    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.exec.device_cache import TRANSFER_STATS
    return {"dispatches": ops.DISPATCH_STATS["dispatches"],
            "programs_compiled": ops.COMPILE_STATS["retraces"],
            "compile_ms": ops.COMPILE_STATS["compile_ms"],
            "h2d_bytes": TRANSFER_STATS["bytes"],
            "jax_lowerings": LOWERINGS[0]}


LOWERINGS = [0]


def count_lowerings(event: str, duration: float, **kw):
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        LOWERINGS[0] += 1


def memory_peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def main():
    t_process = time.monotonic()  # set-up starts here; the imports above are ms
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="rehearsal on the CPU at a tiny scale; counts only")
    args = ap.parse_args()

    bench = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cell = cell_of(bench, args.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(CHECKOUT, cfg_entry["file"]))
    traffic = load_json(os.path.join(ROOT, "traffic", cell["traffic"] + ".json"))
    kind = named("deployments", config["kind"])
    driver = named("drivers", traffic["driver"])
    group = "per_layer" if args.trace else "end_to_end"
    readers = [(m["name"], named("metrics", m["name"]))
               for m in metrics_of(bench, group, cell["name"])]

    if args.dry_run_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell['chips']}")
    import jax
    from galaxysql_tpu import runtime  # the package import enables x64
    cache_dir = runtime.enable_compile_cache()
    # sub-second programs are cached too: a warm start compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.monitoring.register_event_duration_secs_listener(count_lowerings)
    devs = jax.devices()  # a backend that cannot start raises here
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.dry_run_cpu and device["platform"] != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (platform "
                         f"{device['platform']!r}); refusing to run.  "
                         f"--dry-run-cpu is the explicit rehearsal mode.")
    if device["count"] != cell["chips"]:
        raise SystemExit(f"run.py: cell {cell['name']} is defined on "
                         f"{cell['chips']} chip(s), JAX found {device['count']}")

    def say(info: str, **kv):
        print(json.dumps({"info": info, **kv}), flush=True)

    from benchmarks.harness.served import ServedInstance
    out_dir = os.path.join(CHECKOUT, ".bench_out", cell["name"])
    tracer = Tracer(bool(args.trace), os.path.join(out_dir, "trace"))
    served = ServedInstance()
    ctx = Context(served=served, config=config, traffic=traffic, cell=cell,
                  seed=args.seed, dry_run=args.dry_run_cpu, root=ROOT,
                  out_dir=out_dir, device=device)
    try:
        ctx.deployment = kind.load(served, config, args.seed, args.dry_run_cpu)
        say("loaded", **ctx.deployment.timings, cache_dir=cache_dir)
        say("warmed", **driver.warm(ctx))
        window = driver.measure(ctx, args.seconds, tracer)
    finally:
        for undo in reversed(ctx.cleanup):
            undo()
        served.stop()

    ctx.window = window
    ctx.setup_s = window["t_start"] - t_process
    ctx.counts = {k: ctx.counters_end[k] - ctx.counters_start[k]
                  for k in ctx.counters_end}
    ctx.totals = ctx.counters_end
    device["memory_peak_bytes"] = memory_peak_bytes(jax)
    ctx.trace = None
    breakdown = None
    if tracer.ran and not args.dry_run_cpu:
        ctx.trace = T.reduce_trace(T.load_xplane(T.newest_xplane(tracer.log_dir)),
                                   in_flight=window["in_flight"])
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        breakdown = {"device_ops": ctx.trace["device_ops"],
                     "idle_gaps": ctx.trace["idle_gaps"]}

    metrics = {}
    for name, reader in readers:
        if args.dry_run_cpu and reader.SOURCE != "program_counter":
            continue  # a CPU run gives counts and no time, rate or share
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    correct = (window["attempted"] > 0 and window["failed"] == 0
               and ctx.counts["programs_compiled"] == 0)
    say("window", window_s=window["window_s"], counts=ctx.counts,
        first_error=window["first_error"],
        medians={k: stats.median(v)
                 for k, v in window.get("latencies_s", {}).items()})
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if args.dry_run_cpu:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
