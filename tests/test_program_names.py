"""Every `global_jit` program's HLO module is named after its family, and the
benchmark's table of families (`benchmarks/harness/spans.py`) knows every family
the program's source can build."""

import ast
import logging
import os
import re

import jax
import pytest

from benchmarks.harness.spans import FAMILY_GROUP, GROUPS, family_of
from galaxysql_tpu.devtools.checkers.jit_discipline import program_families
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec.compile_cache import GLOBAL_COMPILE_CACHE
from galaxysql_tpu.kernels import relational as K
from galaxysql_tpu.parallel import mpp
from galaxysql_tpu.parallel.mesh import make_mesh
from galaxysql_tpu.parallel.mpp import MppExecutor
from galaxysql_tpu.plan.physical import ExecContext
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(ops.__file__)))
CLOSURE_NAMES = {"jit_run", "jit_spmd", "jit_block", "jit_prog", "jit_picked",
                 "jit__lambda_", "jit_run_rep", "jit_fn", "jit_flat"}
HINT = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "


def module_names(key, program):
    """The names of the HLO modules `program` lowers to (a builder may return
    two programs): lowered from the input signature the compile cache recorded
    at the first call where it has one, else read off the jitted function."""
    seen = GLOBAL_COMPILE_CACHE._observed.get(key)
    if seen is not None and hasattr(program, "lower"):
        treedef, specs = seen
        text = program.lower(
            *jax.tree_util.tree_unflatten(treedef, specs)).as_text()
        return [text.split("\n", 1)[0].split()[1].lstrip("@")]
    return ["jit_" + p.__name__
            for p in (program if isinstance(program, tuple) else (program,))
            if hasattr(p, "lower")]  # a host-np closure has no module


@pytest.fixture(scope="module")
def modules_by_family(tmp_path_factory):
    """Tiny TPC-H Q1/Q3/Q5/Q6 on the local engine (CPU formulation), Q3/Q4/Q5
    on the TPU's sort-based one (Q4's EXISTS is the semi join that builds a
    bloom filter there) and Q3 on four virtual devices, broadcast and
    shuffled; every program then in `_JIT_CACHE`, by family."""
    data = tpch.generate(0.01)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_pylists(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    GLOBAL_COMPILE_CACHE.attach(str(tmp_path_factory.mktemp("aot")))
    cpu_formulation = K.prefer_scatter
    try:
        for q in (1, 3, 5, 6):
            s.execute(HINT + QUERIES[q])
        K.prefer_scatter = lambda: False  # what a TPU traces: join_pairs
        for q in (3, 4, 5):
            s.execute(HINT + QUERIES[q])
        K.prefer_scatter = cpu_formulation
        plan = inst.planner.plan_select(QUERIES[3], "tpch")
        ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                          archive=inst.archive, archive_instance=inst)
        MppExecutor(ctx, make_mesh(4)).execute(plan.rel)
        # and once more with every join down the shuffle exchange
        inst.frag_cache.clear()
        limit, mpp.BROADCAST_BUILD_LIMIT = mpp.BROADCAST_BUILD_LIMIT, 0
        try:
            MppExecutor(ctx, make_mesh(4)).execute(plan.rel)
        finally:
            mpp.BROADCAST_BUILD_LIMIT = limit
        found = {}
        with ops._JIT_CACHE_LOCK:
            cached = list(ops._JIT_CACHE.items())
        for key, program in cached:
            found.setdefault(ops.program_family(key), []).extend(
                module_names(key, program))
    finally:
        K.prefer_scatter = cpu_formulation
        GLOBAL_COMPILE_CACHE.detach()
        s.close()
    return found


@pytest.mark.parametrize("family", [
    "agg_partial", "filter", "segment", "sort",   # local Q1/Q3/Q5/Q6
    "join_pairs", "join_gather", "bloom_query",   # the TPU's join formulation
    "mpp_agg", "mpp_bjoin", "mpp_sjoin",          # Q3 on four devices
])
def test_a_familys_programs_lower_to_modules_named_after_it(
        modules_by_family, family):
    names = modules_by_family.get(family)
    assert names, f"the run built no {family} program: {sorted(modules_by_family)}"
    assert set(names) == {"jit_" + family}


def test_no_cached_program_keeps_its_closures_name(modules_by_family):
    for family, names in modules_by_family.items():
        assert not CLOSURE_NAMES & set(names), (family, names)
        assert all(family_of(n) == family for n in names), (family, names)
        assert family in FAMILY_GROUP, family


@pytest.fixture(scope="module")
def tpch_session():
    data = tpch.generate(0.01, seed=33)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    yield s
    s.close()


class CompiledModules(logging.Handler):
    """The name of every module JAX compiles while it is attached: with
    `jax.clear_caches()` before it, every module a statement dispatches, the
    one-primitive modules of eager `jnp` calls among them."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (\S+) with global shapes", record.getMessage())
        if m:
            self.names.append(m.group(1))

    def __enter__(self):
        self.logger = logging.getLogger(self.LOGGER)
        self.level = self.logger.level
        self.logger.setLevel(logging.DEBUG)
        self.logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        self.logger.removeHandler(self)
        self.logger.setLevel(self.level)


@pytest.mark.parametrize("q", [13, 22, 4])
def test_every_module_a_non_inner_join_dispatches_is_named_after_a_family(
        tpch_session, chip_formulation, monkeypatch, q):
    """Q13's left join with its residual and its NULL-extended rows, Q22's
    scalar cross join and anti join, Q4's semi join, as the chip runs them (AP
    plans over device lanes, build sides that stay on the device): a first
    execution and a second one dispatch `jit_<family>` modules only, no eager
    `jnp` call among them."""
    from galaxysql_tpu.plan import planner
    monkeypatch.setattr(planner, "AP_ROW_THRESHOLD", 1)
    monkeypatch.setattr(ops.HashJoinOp, "BLOOM_MAX_BUILD", 1024)
    jax.clear_caches()
    with CompiledModules() as seen:
        for _ in range(2):
            tpch_session.execute(HINT + QUERIES[q])
    families = {family_of(n.replace("(", "_").rstrip(")")) for n in seen.names}
    assert {"join_pairs", "agg_partial"} <= families, seen.names
    assert families <= set(FAMILY_GROUP), seen.names


def test_jit_program_refuses_a_call_outside_a_builder():
    with pytest.raises(RuntimeError, match="outside a global_jit builder"):
        ops.jit_program(lambda x: x)


def test_a_builder_that_returns_two_programs_names_both():
    key = ("bloom_dev", "test_program_names")
    one, two = ops.global_jit(key, lambda: (ops.jit_program(lambda x: x + 1),
                                            ops.jit_program(lambda x: x * 2)))
    try:
        for program in (one, two):
            assert program.lower(1).as_text().startswith("module @jit_bloom_dev")
    finally:
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.pop(key, None)


def test_the_benchmarks_table_holds_every_family_the_source_can_build():
    families = set()
    for base, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    found, unreadable = program_families(ast.parse(f.read()))
                assert not unreadable, (name, unreadable)
                families |= found
    assert len(families) > 20          # the walk found the program's source
    # a family the source builds and the table lacks is unnamed device time.
    # The table still lists the three `pallas_*` families, which nothing
    # builds since PR 29 and which a PR may not take out of `benchmarks/`
    # while it changes the program: ROADMAP D13 drops them and restores `==`.
    assert families <= set(FAMILY_GROUP)
    assert set(FAMILY_GROUP.values()) == set(GROUPS)
