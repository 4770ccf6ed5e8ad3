"""Process start-up: x64 in one place, the placeable compile cache, and the
documented server entry point serving an aggregate with no caller-side set-up."""

import json
import os
import subprocess
import sys
from decimal import Decimal

import jax
import pytest

from galaxysql_tpu import runtime
from galaxysql_tpu.net.client import MiniClient
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.utils import errors

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    """The caller's environment minus anything that configures JAX."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env.update(extra)
    return env


def test_server_main_serves_wide_sums_without_caller_x64():
    """`python -m galaxysql_tpu.net.server` with nothing in the environment
    enabling x64: a GROUP BY with SUMs past 2^31 comes back exact (with x64
    off the first aggregate died in the uint32 hash constants)."""
    p = subprocess.Popen(
        [sys.executable, "-m", "galaxysql_tpu.net.server", "--port", "0",
         "--platform", "cpu", "--announce"],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=_clean_env(), text=True)
    try:
        line = p.stdout.readline()
        assert line.startswith("SERVER_READY"), line
        c = MiniClient("127.0.0.1", int(line.split()[1]), timeout=120.0)
        c.query("CREATE DATABASE w")
        c.query("USE w")
        c.query("CREATE TABLE t (id BIGINT NOT NULL PRIMARY KEY, g INT NOT "
                "NULL, amt DECIMAL(15,2) NOT NULL, big BIGINT NOT NULL)")
        rows = [(i, i % 2, Decimal(90_000_000) + Decimal(i) / 100,
                 3_000_000_000 + i) for i in range(200)]
        c.query("INSERT INTO t (id, g, amt, big) VALUES " + ", ".join(
            f"({i}, {g}, {amt}, {big})" for i, g, amt, big in rows))
        _, got = c.query("SELECT g, SUM(amt), SUM(big), COUNT(*) FROM t "
                         "GROUP BY g ORDER BY g")
        want = [(g, sum(r[2] for r in rows if r[1] == g),
                 sum(r[3] for r in rows if r[1] == g), 100) for g in (0, 1)]
        assert all(w[1] > 2 ** 31 and w[2] > 2 ** 31 for w in want)
        assert [(int(r[0]), Decimal(r[1]), int(r[2]), int(r[3]))
                for r in got] == want
        c.close()
    finally:
        p.kill()
        p.wait(30)


def test_instance_refuses_to_boot_without_x64():
    assert jax.config.jax_enable_x64  # the package import turned it on
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(errors.TddlError, match="jax_enable_x64"):
            Instance()
    finally:
        jax.config.update("jax_enable_x64", True)


_HELPER_PROBE = """
import json, os, sys
import jax
calls = []
_update = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), _update(k, v))[1]
from galaxysql_tpu import runtime
used = runtime.enable_compile_cache()
jax.config.update = _update
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()
print(json.dumps({"used": used, "calls": calls,
                  "configured": jax.config.jax_compilation_cache_dir}))
"""


def _probe_helper(env):
    out = subprocess.run([sys.executable, "-c", _HELPER_PROBE], cwd=REPO,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_compile_cache_follows_the_environment_variable(tmp_path):
    default_dir = os.path.join(REPO, ".jax_cache")
    before = _listing(default_dir)
    placed = str(tmp_path / "placed")
    got = _probe_helper(_clean_env(JAX_COMPILATION_CACHE_DIR=placed))
    assert got["used"] == placed and got["configured"] == placed
    # set from outside: the helper set nothing in code, and the entries of
    # the compile it ran went under that directory and nowhere else
    assert "jax_compilation_cache_dir" not in got["calls"]
    assert os.listdir(placed)
    assert _listing(default_dir) == before


def test_compile_cache_defaults_to_the_checkout():
    got = _probe_helper(_clean_env())
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.CHECKOUT == REPO
    assert got["used"] == want and got["configured"] == want
    assert os.listdir(want)


def _chip_smoke(*argv, **env):
    return subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                          env=_clean_env(**env), capture_output=True,
                          text=True, timeout=600)


def test_chip_smoke_refuses_a_cpu_and_prints_no_result():
    out = _chip_smoke(JAX_PLATFORMS="cpu")
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_chip_smoke_last_line_is_the_contract_object(tmp_path):
    """The checker reads the LAST stdout line: one JSON object with exactly
    `ok` and `device` {platform, kind, count}; the full report is a line
    before it."""
    out = _chip_smoke("--dry-run-cpu", "--sf", "0.01", JAX_PLATFORMS="cpu",
                      JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    report = json.loads(lines[-2])
    assert report["phase"] == "report" and report["dry_run"] is True
    assert all(q["correct"] for q in report["queries"].values())
    assert report["tp"]["correct"] and report["claim"] is None
