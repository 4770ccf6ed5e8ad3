"""galaxylint + lockdep witness suite (marker: lint; fast target: make lint-smoke).

Covers every lint rule with positive/negative fixture snippets, the pragma and
baseline suppression round-trips, the whole-tree self-run (zero unsuppressed
findings — the same gate `make lint` enforces in CI), and the runtime lockdep
witness: unit cycle-detection plus the failpoint-driven seeded
append_lock/partition-lock inversion caught on a real engine insert ramp.
"""

import ast
import os
import threading

import pytest

import galaxysql_tpu
from galaxysql_tpu.devtools import lint as L
from galaxysql_tpu.devtools.checkers import ALL_CHECKERS
from galaxysql_tpu.devtools.checkers.hygiene import HygieneChecker
from galaxysql_tpu.devtools.checkers.lock_order import LockOrderChecker
from galaxysql_tpu.utils import lockdep
from galaxysql_tpu.utils.failpoint import FAIL_POINTS, FP_LOCK_INVERT

pytestmark = pytest.mark.lint


def rules_of(findings, suppressed=False):
    return sorted({f.rule for f in findings
                   if bool(f.suppressed) == suppressed})


# -- lock-order / lock-blocking ------------------------------------------------

class TestLockOrderRule:
    def test_inversion_flagged(self):
        fs = L.lint_source(
            "def f(store, p):\n"
            "    with p.lock:\n"
            "        with store.append_lock:\n"
            "            pass\n",
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == ["lock-order"]

    def test_canonical_order_clean(self):
        fs = L.lint_source(
            "def f(store, p, metadb):\n"
            "    with store.append_lock, p.lock:\n"
            "        metadb.kv_put('k', 'v')\n"
            "    with p.lock:\n"
            "        pass\n",
            "galaxysql_tpu/storage/x.py")
        # the metadb IO under the partition lock is a lock-blocking warn,
        # but the ORDER is canonical: no lock-order finding
        assert "lock-order" not in rules_of(fs)

    def test_multi_item_with_orders_left_to_right(self):
        fs = L.lint_source(
            "def f(store, p):\n"
            "    with p.lock, store.append_lock:\n"
            "        pass\n",
            "galaxysql_tpu/txn/x.py")
        assert rules_of(fs) == ["lock-order"]

    def test_one_level_call_propagation(self):
        fs = L.lint_source(
            "def helper(self):\n"
            "    with self.append_lock:\n"
            "        pass\n"
            "class MetaDb:\n"
            "    def g(self):\n"
            "        with self._lock:\n"
            "            self.helper()\n",
            "galaxysql_tpu/meta/x.py")
        assert any(f.rule == "lock-order" and "via call to helper" in f.message
                   for f in fs)

    def test_two_same_class_locks_flagged(self):
        fs = L.lint_source(
            "def f(p, part):\n"
            "    with p.lock:\n"
            "        with part.lock:\n"
            "            pass\n",
            "galaxysql_tpu/storage/x.py")
        assert any(f.rule == "lock-order" and "intra-class" in f.message
                   for f in fs)

    def test_reentrant_same_expr_clean(self):
        fs = L.lint_source(
            "class Partition:\n"
            "    def f(self):\n"
            "        with self.lock:\n"
            "            with self.lock:\n"
            "                pass\n",
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == []

    def test_blocking_ops_under_hot_lock(self):
        fs = L.lint_source(
            "import time\n"
            "def f(store, client, metadb):\n"
            "    with store.append_lock:\n"
            "        time.sleep(0.1)\n"
            "        client.request({})\n"
            "        metadb.execute('x')\n"
            "    time.sleep(0.1)\n",  # outside: clean
            "galaxysql_tpu/server/x.py")
        blocking = [f for f in fs if f.rule == "lock-blocking"]
        assert len(blocking) == 3
        assert all(f.line in (4, 5, 6) for f in blocking)

    def test_out_of_scope_dir_ignored(self):
        fs = L.lint_source(
            "def f(store, p):\n"
            "    with p.lock:\n"
            "        with store.append_lock:\n"
            "            pass\n",
            "galaxysql_tpu/plan/x.py")
        assert [f for f in fs if f.rule.startswith("lock-")] == []


# -- jit-raw / jit-device-sync -------------------------------------------------

class TestJitRules:
    def test_raw_jit_flagged(self):
        fs = L.lint_source(
            "import jax\n"
            "def f():\n"
            "    return jax.jit(lambda x: x)\n",
            "galaxysql_tpu/exec/x.py")
        assert rules_of(fs) == ["jit-raw"]

    def test_builder_closure_clean(self):
        fs = L.lint_source(
            "def op(key):\n"
            "    def build():\n"
            "        def run(x):\n"
            "            return x\n"
            "        return jit_program(run)\n"
            "    return global_jit(key, build)\n"
            "def op2(key):\n"
            "    return global_jit(key, lambda: ops.jit_program(lambda x: x))\n",
            "galaxysql_tpu/exec/x.py")
        assert rules_of(fs) == []

    @pytest.mark.parametrize("jit,where", [
        ("jax.jit", "build"),          # bare jit in a builder: module jit_run
        ("jit_program", "elsewhere"),  # the helper outside a builder
        ("ops.jit_program", "elsewhere"),
    ])
    def test_unnamed_or_escaped_program_flagged(self, jit, where):
        fs = L.lint_source(
            "import jax\n"
            "def op(key):\n"
            f"    def {where}():\n"
            "        def run(x):\n"
            "            return x\n"
            f"        return {jit}(run)\n"
            "    return global_jit(key, build)\n",
            "galaxysql_tpu/exec/x.py")
        assert rules_of(fs) == ["jit-raw"]

    @pytest.mark.parametrize("key,rules", [
        ('("join_pairs", cap)', []),
        ('("filter-np",) + rest', []),
        ('(backend, "prod") + rest', []),      # a fused segment's key
        ('(family_of(x), cap)', ["jit-family"]),
        ('make_key(x)', ["jit-family"]),
    ])
    def test_key_family_readable_from_source(self, key, rules):
        fs = L.lint_source(
            "def op(x, cap, rest, backend):\n"
            f"    key = {key}\n"
            "    def build():\n"
            "        return jit_program(lambda a: a)\n"
            "    return global_jit(key, build)\n",
            "galaxysql_tpu/exec/x.py")
        assert rules_of(fs) == rules

    def test_raw_pallas_call_flagged(self):
        fs = L.lint_source(
            "from jax.experimental import pallas as pl\n"
            "def f(shape):\n"
            "    return pl.pallas_call(lambda r, o: None, out_shape=shape)\n",
            "galaxysql_tpu/kernels/x.py")
        assert rules_of(fs) == ["pallas-raw"]

    def test_pallas_call_in_builder_clean(self):
        fs = L.lint_source(
            "from jax.experimental import pallas as pl\n"
            "def wrap(key, shape):\n"
            "    def build():\n"
            "        def kernel(r, o):\n"
            "            pass\n"
            "        return pl.pallas_call(kernel, out_shape=shape)\n"
            "    return global_jit(key, build)\n",
            "galaxysql_tpu/kernels/x.py")
        assert rules_of(fs) == []

    def test_device_sync_in_hot_dir_flagged(self):
        fs = L.lint_source(
            "def drain(v):\n"
            "    return v.item()\n"
            "def wait(v):\n"
            "    v.block_until_ready()\n",
            "galaxysql_tpu/exec/x.py")
        assert len([f for f in fs if f.rule == "jit-device-sync"]) == 2

    def test_profiling_scope_allowlisted(self):
        fs = L.lint_source(
            "def profile_drain(v):\n"
            "    return v.item()\n"
            "class Bench:\n"
            "    def run(self, v):\n"
            "        return v.item()\n",  # Bench.run matches 'bench'
            "galaxysql_tpu/exec/x.py")
        assert rules_of(fs) == []

    def test_cold_dir_ignored(self):
        fs = L.lint_source(
            "def f(v):\n"
            "    return v.item()\n",
            "galaxysql_tpu/meta/x.py")
        assert rules_of(fs) == []


# -- swallow / untyped-raise ---------------------------------------------------

class TestTypedErrorRules:
    def test_silent_swallow_flagged(self):
        fs = L.lint_source(
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n"
            "def h():\n"
            "    for i in x:\n"
            "        try:\n"
            "            g()\n"
            "        except Exception:\n"
            "            continue\n",
            "galaxysql_tpu/net/x.py")
        assert len([f for f in fs if f.rule == "swallow"]) == 2

    def test_handled_swallows_clean(self):
        fs = L.lint_source(
            "def a():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        raise errors.TddlError('x')\n"
            "def b():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception as e:\n"
            "        events.publish('boom', str(e))\n"
            "def c(out):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception as e:\n"
            "        out['err'] = e\n",  # records the exception: handled
            "galaxysql_tpu/net/x.py",
            test_text="boom")  # the published kind is test-covered here
        assert rules_of(fs) == []

    def test_untyped_raise_flagged_on_ramp_only(self):
        src = ("def f():\n"
               "    raise ValueError('boom')\n")
        assert rules_of(L.lint_source(src, "galaxysql_tpu/server/x.py")) == \
            ["untyped-raise"]
        assert rules_of(L.lint_source(src, "galaxysql_tpu/expr/x.py")) == []

    def test_typed_raise_clean(self):
        fs = L.lint_source(
            "def f():\n"
            "    raise errors.QueryTimeoutError('deadline')\n",
            "galaxysql_tpu/server/x.py")
        assert rules_of(fs) == []


# -- hygiene (cross-file) ------------------------------------------------------

class TestHygieneRules:
    def _project(self, srcs, test_text=""):
        mods = [L.Module(p, s) for p, s in srcs]
        return L.Project("", mods, test_text)

    def test_dead_failpoint_flagged(self):
        proj = self._project(
            [("galaxysql_tpu/utils/fp.py", 'FP_NEVER = "FP_NEVER"\n')])
        fs = list(HygieneChecker().finalize(proj))
        assert [f.rule for f in fs] == ["dead-failpoint"]

    def test_armed_failpoint_clean(self):
        proj = self._project(
            [("galaxysql_tpu/utils/fp.py", 'FP_USED = "FP_USED"\n')],
            test_text='FAIL_POINTS.arm(FP_USED)\n')
        assert list(HygieneChecker().finalize(proj)) == []

    def test_failpoint_prefix_of_covered_key_still_dead(self):
        """FP_RPC_DELAY must not count as covered just because tests arm
        FP_RPC_DELAY_MS (word-boundary, not substring, matching)."""
        proj = self._project(
            [("galaxysql_tpu/utils/fp.py",
              'FP_RPC_DELAY = "FP_RPC_DELAY"\n')],
            test_text='FAIL_POINTS.arm(FP_RPC_DELAY_MS, 5)\n')
        fs = list(HygieneChecker().finalize(proj))
        assert [f.rule for f in fs] == ["dead-failpoint"]

    def test_metric_orphans(self):
        metrics = ("DEAD = Counter('dead', 'never updated')\n"
                   "HIDDEN = Counter('hidden', 'never adopted')\n"
                   "GOOD = Counter('good', 'updated and adopted')\n"
                   "HIDDEN.inc()\n"
                   "GOOD.inc()\n")
        inst = ("def boot(reg):\n"
                "    reg.adopt(DEAD)\n"
                "    reg.adopt(GOOD)\n")
        proj = self._project(
            [("galaxysql_tpu/utils/m.py", metrics),
             ("galaxysql_tpu/server/i.py", inst)])
        fs = list(HygieneChecker().finalize(proj))
        assert len(fs) == 2
        assert any("DEAD" in f.message and "never updated" in f.message
                   for f in fs)
        assert any("HIDDEN" in f.message and "never adopted" in f.message
                   for f in fs)
        assert all(f.rule == "metric-orphan" for f in fs)

    # -- event-uncorrelated: trigger-kind publishes must carry digest/trace_id

    def test_uncorrelated_trigger_event_flagged(self):
        fs = L.lint_source(
            "def trip(events, worker):\n"
            "    events.publish('breaker_open', 'worker tripped',\n"
            "                   worker=worker)\n",
            "galaxysql_tpu/server/x.py",
            test_text="breaker_open")  # kind is test-covered; only the
        assert rules_of(fs) == ["event-uncorrelated"]  # correlation is missing

    def test_correlated_trigger_event_clean(self):
        fs = L.lint_source(
            "def regress(events, d, tid):\n"
            "    events.publish('plan_regression', 'plan got slower',\n"
            "                   digest=d)\n"
            "    events.publish('slo_burn', 'window burning',\n"
            "                   trace_id=tid)\n",
            "galaxysql_tpu/server/x.py",
            test_text="plan_regression slo_burn")
        assert "event-uncorrelated" not in rules_of(fs)

    def test_trigger_event_splat_unchecked(self):
        # **kwargs may carry the keys — statically unverifiable, so clean
        fs = L.lint_source(
            "def fwd(events, kw):\n"
            "    events.publish('columnar_tail_failed', 'tail', **kw)\n",
            "galaxysql_tpu/server/x.py",
            test_text="columnar_tail_failed")
        assert "event-uncorrelated" not in rules_of(fs)

    def test_nontrigger_kind_not_checked(self):
        fs = L.lint_source(
            "def note(events):\n"
            "    events.publish('gc_pause', 'background sweep')\n",
            "galaxysql_tpu/server/x.py",
            test_text="gc_pause")
        assert "event-uncorrelated" not in rules_of(fs)

    def test_uncorrelated_pragma_suppresses(self):
        fs = L.lint_source(
            "def trip(events):\n"
            "    events.publish('breaker_open', 'no query context')"
            "  # galaxylint: disable=event-uncorrelated"
            " -- background health loop, no statement to implicate\n",
            "galaxysql_tpu/server/x.py",
            test_text="breaker_open")
        assert "event-uncorrelated" not in rules_of(fs)
        assert "event-uncorrelated" in rules_of(fs, suppressed=True)


# -- pragmas -------------------------------------------------------------------

class TestPragmas:
    SRC = ("def f(store, p):\n"
           "    with p.lock:\n"
           "        with store.append_lock:{pragma}\n"
           "            pass\n")

    def test_justified_pragma_suppresses(self):
        fs = L.lint_source(self.SRC.format(
            pragma="  # galaxylint: disable=lock-order -- seeded inversion"),
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == []                       # nothing unsuppressed
        assert rules_of(fs, suppressed=True) == ["lock-order"]

    def test_unjustified_pragma_suppresses_nothing(self):
        fs = L.lint_source(self.SRC.format(
            pragma="  # galaxylint: disable=lock-order"),
            "galaxysql_tpu/storage/x.py")
        open_rules = rules_of(fs)
        assert "pragma-justify" in open_rules
        assert "lock-order" in open_rules  # NOT suppressed without a why

    def test_wrong_rule_pragma_does_not_suppress(self):
        fs = L.lint_source(self.SRC.format(
            pragma="  # galaxylint: disable=swallow -- wrong rule"),
            "galaxysql_tpu/storage/x.py")
        open_rules = rules_of(fs)
        assert "lock-order" in open_rules
        # and the useless pragma is itself flagged
        assert "pragma-unknown" in open_rules

    def test_stale_pragma_flagged(self):
        """A pragma on a line where nothing fires (typo'd rule name or the
        finding was fixed) must not look like safety."""
        fs = L.lint_source(
            "def f():\n"
            "    x = 1  # galaxylint: disable=lock-ordr -- typo'd rule\n",
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == ["pragma-unknown"]

    def test_file_level_pragma(self):
        fs = L.lint_source(
            "# galaxylint: disable-file=lock-order -- fixture file\n" +
            self.SRC.format(pragma=""),
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == []

    def test_file_level_pragma_hygiene(self):
        # unjustified file pragma: flagged even with no finding in the file
        fs = L.lint_source(
            "# galaxylint: disable-file=swallow\n"
            "X = 1\n",
            "galaxysql_tpu/storage/x.py")
        assert "pragma-justify" in rules_of(fs)
        # justified but nothing fires: stale, delete it
        fs = L.lint_source(
            "# galaxylint: disable-file=swallow -- nothing here\n"
            "X = 1\n",
            "galaxysql_tpu/storage/x.py")
        assert rules_of(fs) == ["pragma-unknown"]


# -- baseline ------------------------------------------------------------------

class TestBaseline:
    def _findings(self):
        return L.lint_source(
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n",
            "galaxysql_tpu/net/x.py")

    def test_round_trip_suppresses(self):
        fs = self._findings()
        entries = [{"rule": f.rule, "path": f.path, "qualname": f.qualname,
                    "line_text": f.line_text, "why": "grandfathered"}
                   for f in fs]
        out = L.apply_baseline(self._findings(), entries)
        assert rules_of(out) == []
        assert rules_of(out, suppressed=True) == ["swallow"]

    def test_stale_entry_flagged(self):
        entries = [{"rule": "swallow", "path": "galaxysql_tpu/net/x.py",
                    "qualname": "gone", "line_text": "except Exception:",
                    "why": "was fixed"}]
        out = L.apply_baseline(self._findings(), entries)
        assert "baseline-stale" in rules_of(out)

    def test_unjustified_entry_suppresses_nothing(self):
        fs = self._findings()
        entries = [{"rule": f.rule, "path": f.path, "qualname": f.qualname,
                    "line_text": f.line_text, "why": ""} for f in fs]
        out = L.apply_baseline(self._findings(), entries)
        assert "swallow" in rules_of(out)           # NOT suppressed
        assert "baseline-justify" in rules_of(out)

    def test_save_load_round_trip(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        entries = [{"rule": "swallow", "path": "a.py", "qualname": "f",
                    "line_text": "except Exception:", "why": "because"}]
        L.save_baseline(path, entries)
        assert L.load_baseline(path) == entries


# -- whole-tree self-run -------------------------------------------------------

class TestTreeClean:
    def test_zero_unsuppressed_findings(self):
        """The same gate `make lint` enforces: the committed tree + baseline
        + pragmas lint clean."""
        findings = L.collect()
        open_fs = [f for f in findings if not f.suppressed]
        assert open_fs == [], "\n".join(f.render() for f in open_fs)

    def test_every_suppression_is_justified(self):
        for e in L.load_baseline(L.BASELINE_PATH):
            assert e.get("why"), f"unjustified baseline entry: {e}"

    def test_rules_registered(self):
        rules = {r for ck in ALL_CHECKERS for r in ck.rules}
        assert rules == {"lock-order", "lock-blocking", "jit-raw",
                         "jit-family", "pallas-raw", "jit-device-sync",
                         "swallow",
                         "untyped-raise", "dead-failpoint", "metric-orphan",
                         "event-untested", "histogram-unsampled",
                         "event-uncorrelated"}

    def test_cli_exits_zero(self, capsys):
        assert L.main([]) == 0
        assert "0 finding(s)" in capsys.readouterr().out


# -- layering: the bottom boxes of the drawing --------------------------------

def imported_packages(box):
    """The `galaxysql_tpu` packages (or top-level modules) that the modules of
    `galaxysql_tpu/<box>/` import, at any depth of any function, read by `ast`;
    the box itself left out."""
    root = os.path.join(os.path.dirname(galaxysql_tpu.__file__), box)
    found, modules = set(), 0
    for base, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            modules += 1
            with open(os.path.join(base, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    assert node.level == 0, (name, "imports are absolute here")
                    names = [f"{node.module}.{a.name}" for a in node.names]
                else:
                    continue
                found |= {n.split(".")[1] for n in names
                          if n.startswith("galaxysql_tpu.")}
    assert modules, root  # the walk found the box's source
    return found - {box}


@pytest.mark.parametrize("box,may_import", [
    ("config", set()), ("kernels", {"runtime"}), ("types", {"utils"}),
    ("chunk", {"types"})])
def test_a_bottom_box_imports_only_what_lies_under_it(box, may_import):
    """`kernels/` knows shapes and lanes and where a program runs, nothing of
    the executor or the session above it (it imported `exec` until PR 29);
    the other three arrows are what the tree does and nothing else checked."""
    assert imported_packages(box) <= may_import


# -- lockdep witness (runtime) -------------------------------------------------

@pytest.fixture()
def armed_lockdep():
    lockdep.enable()
    lockdep.WITNESS.reset()
    yield lockdep.WITNESS
    lockdep.disable()
    lockdep.WITNESS.reset()
    FAIL_POINTS.clear()


class TestLockdepUnit:
    def test_disarmed_returns_plain_lock(self):
        assert not lockdep.enabled() or True  # env may arm the whole run
        if not lockdep.enabled():
            lk = lockdep.named_lock("x")
            assert not hasattr(lk, "dep_name")

    def test_consistent_order_clean(self, armed_lockdep):
        a, b, c = (lockdep.named_lock(n) for n in ("la", "lb", "lc"))
        for _ in range(3):
            with a:
                with b:
                    with c:
                        pass
        armed_lockdep.assert_clean()
        assert ("la", "lb") in armed_lockdep.edges()

    def test_inversion_raises(self, armed_lockdep):
        a, b = lockdep.named_lock("ia"), lockdep.named_lock("ib")
        with a:
            with b:
                pass
        with pytest.raises(lockdep.LockOrderViolation, match="inverts"):
            with b:
                with a:
                    pass
        assert armed_lockdep.violations

    def test_three_lock_cycle(self, armed_lockdep):
        a, b, c = (lockdep.named_lock(n) for n in ("ca", "cb", "cc"))
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with pytest.raises(lockdep.LockOrderViolation):
            with c:
                with a:
                    pass

    def test_reentrant_instance_ok(self, armed_lockdep):
        a = lockdep.named_lock("ra")
        with a:
            with a:
                pass
        armed_lockdep.assert_clean()

    def test_same_class_two_instances_raises(self, armed_lockdep):
        a1, a2 = lockdep.named_lock("pp"), lockdep.named_lock("pp")
        with pytest.raises(lockdep.LockOrderViolation, match="intra-class"):
            with a1:
                with a2:
                    pass

    def test_violation_does_not_wedge(self, armed_lockdep):
        """The inverted lock is never acquired — the thread holds nothing
        extra afterwards and other threads proceed."""
        a, b = lockdep.named_lock("wa"), lockdep.named_lock("wb")
        with a:
            with b:
                pass
        with pytest.raises(lockdep.LockOrderViolation):
            with b:
                with a:
                    pass
        done = []
        t = threading.Thread(target=lambda: (a.acquire(), a.release(),
                                             done.append(1)))
        t.start()
        t.join(5)
        assert done == [1]


class TestLockdepSeeded:
    def test_seeded_inversion_caught_on_insert_ramp(self, armed_lockdep):
        """FP_LOCK_INVERT drives a deliberate partition->append_lock
        acquisition on the real insert ramp; the witness must trip — and a
        disarmed re-run of the identical statement must pass clean."""
        from galaxysql_tpu.server.instance import Instance
        from galaxysql_tpu.server.session import Session
        inst = Instance()
        s = Session(inst)
        try:
            s.execute("CREATE DATABASE ld")
            s.execute("USE ld")
            s.execute("CREATE TABLE t (a BIGINT, b BIGINT) "
                      "PARTITION BY HASH(a) PARTITIONS 2")
            # normal insert: establishes the canonical append->partition edge
            s.execute("INSERT INTO t VALUES (1, 10)")
            armed_lockdep.assert_clean()
            assert any(a == "append_lock" and b.startswith("partition")
                       for a, b in armed_lockdep.edges())
            FAIL_POINTS.arm(FP_LOCK_INVERT, True)
            with pytest.raises(lockdep.LockOrderViolation):
                s.execute("INSERT INTO t VALUES (2, 20)")
            assert armed_lockdep.violations
            # disarmed: the same statement goes through clean
            FAIL_POINTS.clear()
            armed_lockdep.violations.clear()
            s.execute("INSERT INTO t VALUES (3, 30)")
            assert s.execute("SELECT count(*) FROM t").rows[0][0] >= 2
            armed_lockdep.assert_clean()
        finally:
            s.close()

    def test_canonical_write_path_clean(self, armed_lockdep):
        """A write-heavy mixed workload (insert/update/delete + GSI) records
        only DAG edges — every concurrency test doubles as this proof when
        GALAXYSQL_LOCKDEP=1 (the dml/chaos/batch smoke wiring)."""
        from galaxysql_tpu.server.instance import Instance
        from galaxysql_tpu.server.session import Session
        inst = Instance()
        s = Session(inst)
        try:
            s.execute("CREATE DATABASE lw")
            s.execute("USE lw")
            s.execute("CREATE TABLE w (a BIGINT, b BIGINT) "
                      "PARTITION BY HASH(a) PARTITIONS 4")
            s.execute("CREATE GLOBAL INDEX gw ON w (b)")
            for i in range(8):
                s.execute(f"INSERT INTO w VALUES ({i}, {i * 10})")
            s.execute("UPDATE w SET b = 99 WHERE a = 3")
            s.execute("DELETE FROM w WHERE a = 5")
            assert s.execute("SELECT count(*) FROM w").rows == [(7,)]
            armed_lockdep.assert_clean()
        finally:
            s.close()
