"""`run.py` end to end here: the refusal without a chip, and the explicit
`--dry-run-cpu` rehearsal of every cell at a tiny scale, the four-chip cell on
four virtual CPU devices, each ending in a well-formed result line."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "benchmarks/run.py", *argv],
                          cwd=CHECKOUT, env=env, capture_output=True,
                          text=True, timeout=900)


def test_refuses_a_machine_without_a_tpu_and_prints_no_result():
    out = run("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "refusing to run" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if '"correct"' in ln]


def test_unknown_workload_is_an_error():
    out = run("--workload", "nope", "--seed", "1", "--seconds", "1",
              "--trace", "0", "--dry-run-cpu")
    assert out.returncode != 0 and "no workload" in out.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_dry_run_ends_in_a_well_formed_result_line(cell, trace):
    out = run("--workload", cell, "--seed", "2147483659", "--seconds", "2",
              "--trace", str(trace), "--dry-run-cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["dry_run"] is True
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # a CPU run carries counts and no time, rate or share of a device
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name, m in result["metrics"].items():
        assert by_name[name]["source"] == "program_counter", name
        assert set(m) == {"value", "unit"} and m["unit"] == by_name[name]["unit"]
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
